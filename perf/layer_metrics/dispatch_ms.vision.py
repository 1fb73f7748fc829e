"""``dispatch_ms.vision``: see ``perf.layer_readers.dispatch_ms``."""

from perf.layer_readers import dispatch_ms as read  # noqa: F401
