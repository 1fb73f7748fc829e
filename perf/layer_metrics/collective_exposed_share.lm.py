"""``collective_exposed_share.lm``: see ``perf.layer_readers.collective_exposed_share``."""

from perf.layer_readers import collective_exposed_share as read  # noqa: F401
