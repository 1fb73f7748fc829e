"""``unattributed_share.scope_vision``: see ``perf.scope_shares.unattributed_share``."""

from perf.scope_shares import unattributed_share as read  # noqa: F401
