"""``flash_time_share.lm``: see ``perf.layer_readers.flash_time_share``."""

from perf.layer_readers import flash_time_share as read  # noqa: F401
