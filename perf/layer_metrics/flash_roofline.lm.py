"""``flash_roofline.lm``: see ``perf.layer_readers.flash_roofline``."""

from perf.layer_readers import flash_roofline as read  # noqa: F401
