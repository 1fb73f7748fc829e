"""``swa_fwd_roofline.swa``: see ``perf.window_rooflines.kernel_roofline``."""

from perf.window_rooflines import kernel_roofline


def read(run):
    return kernel_roofline(run, "swa_fwd")
