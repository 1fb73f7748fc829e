"""``setup_compile_s``: see ``perf.program_spans.setup_seconds``."""

from perf.program_spans import setup_seconds


def read(run):
    return setup_seconds(run, "compile")
