"""``backward_share.scope_vision``: see ``perf.scope_shares.backward_share``."""

from perf.scope_shares import backward_share as read  # noqa: F401
