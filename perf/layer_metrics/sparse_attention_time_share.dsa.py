"""``sparse_attention_time_share.dsa``: see ``perf.dsa_rooflines.sparse_attention_time_share``."""

from perf.dsa_rooflines import sparse_attention_time_share as read  # noqa: F401
