"""``state_decay_mean.gdn``: see ``perf.gdn_rooflines.state_decay_mean``."""

from perf.gdn_rooflines import state_decay_mean as read  # noqa: F401
