"""``attention_kernels_time_share.swa``: see ``perf.window_rooflines.attention_kernels_time_share``."""

from perf.window_rooflines import attention_kernels_time_share as read  # noqa: F401
