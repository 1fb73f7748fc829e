"""``ssd_fwd_roofline.hybrid``: see ``perf.ssd_rooflines.ssd_kernel_roofline``."""

from perf.ssd_rooflines import ssd_kernel_roofline


def read(run):
    return ssd_kernel_roofline(run, "ssd_fwd")
