"""``short_conv_fwd_roofline.conv``: see ``perf.conv_rooflines.kernel_roofline``."""

from perf.conv_rooflines import kernel_roofline


def read(run):
    return kernel_roofline(run, "short_conv_fwd")
