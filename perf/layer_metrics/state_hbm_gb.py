"""``state_hbm_gb``: see ``perf.memory_shares.state_hbm_gb``."""

from perf.memory_shares import state_hbm_gb as read  # noqa: F401
