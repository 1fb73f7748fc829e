"""``input_wait_share.lm``: see ``perf.layer_readers.input_wait_share``."""

from perf.layer_readers import input_wait_share as read  # noqa: F401
