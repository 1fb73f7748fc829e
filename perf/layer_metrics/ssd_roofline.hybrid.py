"""``ssd_roofline.hybrid``: see ``perf.ssd_rooflines.ssd_roofline``."""

from perf.ssd_rooflines import ssd_roofline as read  # noqa: F401
