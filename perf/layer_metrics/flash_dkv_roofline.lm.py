"""``flash_dkv_roofline.lm``: see ``perf.kernel_rooflines.flash_kernel_roofline``."""

from perf.kernel_rooflines import flash_kernel_roofline


def read(run):
    return flash_kernel_roofline(run, "flash_dkv")
