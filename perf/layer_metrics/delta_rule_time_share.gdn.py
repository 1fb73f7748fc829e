"""``delta_rule_time_share.gdn``: see ``perf.gdn_rooflines.delta_rule_time_share``."""

from perf.gdn_rooflines import delta_rule_time_share as read  # noqa: F401
