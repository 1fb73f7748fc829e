"""``producer_batch_ms.lm``: see ``perf.program_spans.producer_batch_ms``."""

from perf.program_spans import producer_batch_ms as read  # noqa: F401
