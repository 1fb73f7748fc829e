"""``step_device_ms.lm``: see ``perf.layer_readers.step_device_ms``."""

from perf.layer_readers import step_device_ms as read  # noqa: F401
