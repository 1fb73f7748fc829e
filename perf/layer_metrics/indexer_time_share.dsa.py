"""``indexer_time_share.dsa``: see ``perf.dsa_rooflines.indexer_time_share``."""

from perf.dsa_rooflines import indexer_time_share as read  # noqa: F401
