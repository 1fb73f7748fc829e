"""``hbm_unexplained_gb``: see ``perf.memory_shares.hbm_unexplained_gb``."""

from perf.memory_shares import hbm_unexplained_gb as read  # noqa: F401
