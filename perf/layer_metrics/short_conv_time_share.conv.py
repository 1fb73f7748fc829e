"""``short_conv_time_share.conv``: see ``perf.conv_rooflines.short_conv_time_share``."""

from perf.conv_rooflines import short_conv_time_share as read  # noqa: F401
