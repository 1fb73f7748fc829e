"""``ssd_time_share.hybrid``: see ``perf.ssd_rooflines.ssd_time_share``."""

from perf.ssd_rooflines import ssd_time_share as read  # noqa: F401
