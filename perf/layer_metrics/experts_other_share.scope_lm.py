"""``experts_other_share.scope_lm``: see ``perf.scope_shares.experts_other_share``."""

from perf.scope_shares import experts_other_share as read  # noqa: F401
