"""``window_attention_time_share.swa``: see ``perf.window_rooflines.window_attention_time_share``."""

from perf.window_rooflines import window_attention_time_share as read  # noqa: F401
