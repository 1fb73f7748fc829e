"""``fused_across_share.scope_lm``: see ``perf.scope_shares.fused_across_share``."""

from perf.scope_shares import fused_across_share as read  # noqa: F401
