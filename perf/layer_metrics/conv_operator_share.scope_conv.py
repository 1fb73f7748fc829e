"""``conv_operator_share.scope_conv``: see ``perf.conv_rooflines.conv_operator_share``."""

from perf.conv_rooflines import conv_operator_share as read  # noqa: F401
