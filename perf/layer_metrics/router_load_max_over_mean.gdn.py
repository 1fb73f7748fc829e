"""``router_load_max_over_mean.gdn``: see ``perf.expert_rooflines.router_load_max_over_mean``."""

from perf.expert_rooflines import router_load_max_over_mean as read  # noqa: F401
