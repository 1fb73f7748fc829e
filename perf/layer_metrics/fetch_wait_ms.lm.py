"""``fetch_wait_ms.lm``: see ``perf.program_spans.fetch_wait_ms``."""

from perf.program_spans import fetch_wait_ms as read  # noqa: F401
