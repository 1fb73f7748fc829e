"""``expert_gmm_dx_roofline.lm``: see ``perf.expert_rooflines.expert_kernel_roofline``."""

from perf.expert_rooflines import expert_kernel_roofline


def read(run):
    return expert_kernel_roofline(run, "expert_gmm_dx")
