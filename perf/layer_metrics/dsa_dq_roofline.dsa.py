"""``dsa_dq_roofline.dsa``: see ``perf.dsa_rooflines.kernel_roofline``."""

from perf.dsa_rooflines import kernel_roofline


def read(run):
    return kernel_roofline(run, "dsa_dq")
