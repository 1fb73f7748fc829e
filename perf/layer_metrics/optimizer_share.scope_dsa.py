"""``optimizer_share.scope_dsa``: see ``perf.scope_shares.optimizer_share``."""

from perf.scope_shares import optimizer_share as read  # noqa: F401
