"""``producer_busy_share.lm``: see ``perf.program_spans.producer_busy_share``."""

from perf.program_spans import producer_busy_share as read  # noqa: F401
