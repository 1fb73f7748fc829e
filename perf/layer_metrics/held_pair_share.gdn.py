"""``held_pair_share.gdn``: see ``perf.ssd_rooflines.held_pair_share``."""

from perf.ssd_rooflines import held_pair_share as read  # noqa: F401
