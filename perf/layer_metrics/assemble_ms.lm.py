"""``assemble_ms.lm``: mean ``assemble`` per dispatch (``perf.program_spans``)."""

from perf.program_spans import mean_ms_per_dispatch


def read(run):
    return mean_ms_per_dispatch(run, "assemble")
