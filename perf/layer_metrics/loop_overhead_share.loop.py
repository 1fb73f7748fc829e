"""``loop_overhead_share.loop``: see ``perf.loop_shares.loop_overhead_share``."""

from perf.loop_shares import loop_overhead_share as read  # noqa: F401
