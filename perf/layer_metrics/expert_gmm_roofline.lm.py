"""``expert_gmm_roofline.lm``: see ``perf.expert_rooflines.expert_gmm_roofline``."""

from perf.expert_rooflines import expert_gmm_roofline as read  # noqa: F401
