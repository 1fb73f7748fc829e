"""``delta_bwd_roofline.gdn``: see ``perf.gdn_rooflines.delta_kernel_roofline``."""

from perf.gdn_rooflines import delta_kernel_roofline


def read(run):
    return delta_kernel_roofline(run, "gdn_bwd")
