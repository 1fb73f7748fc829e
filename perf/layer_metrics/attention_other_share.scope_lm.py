"""``attention_other_share.scope_lm``: see ``perf.scope_shares.attention_other_share``."""

from perf.scope_shares import attention_other_share as read  # noqa: F401
