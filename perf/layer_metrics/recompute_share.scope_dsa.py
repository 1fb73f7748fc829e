"""``recompute_share.scope_dsa``: see ``perf.scope_shares.recompute_share``."""

from perf.scope_shares import recompute_share as read  # noqa: F401
