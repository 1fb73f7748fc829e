"""``linear_attention_operator_share.scope_gdn``: see ``perf.gdn_rooflines.linear_attention_operator_share``."""

from perf.gdn_rooflines import linear_attention_operator_share as read  # noqa: F401
