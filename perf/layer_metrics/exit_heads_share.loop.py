"""``exit_heads_share.loop``: see ``perf.loop_shares.exit_heads_share``."""

from perf.loop_shares import exit_heads_share as read  # noqa: F401
