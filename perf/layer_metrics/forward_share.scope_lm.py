"""``forward_share.scope_lm``: see ``perf.scope_shares.forward_share``."""

from perf.scope_shares import forward_share as read  # noqa: F401
