"""``block_other_share.scope_lm``: see ``perf.scope_shares.block_other_share``."""

from perf.scope_shares import block_other_share as read  # noqa: F401
