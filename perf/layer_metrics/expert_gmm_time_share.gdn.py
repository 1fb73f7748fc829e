"""``expert_gmm_time_share.gdn``: see ``perf.expert_rooflines.expert_gmm_time_share``."""

from perf.expert_rooflines import expert_gmm_time_share as read  # noqa: F401
