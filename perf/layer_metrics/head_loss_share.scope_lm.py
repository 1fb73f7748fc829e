"""``head_loss_share.scope_lm``: see ``perf.scope_shares.head_loss_share``."""

from perf.scope_shares import head_loss_share as read  # noqa: F401
