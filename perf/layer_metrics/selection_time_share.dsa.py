"""``selection_time_share.dsa``: see ``perf.dsa_rooflines.selection_time_share``."""

from perf.dsa_rooflines import selection_time_share as read  # noqa: F401
