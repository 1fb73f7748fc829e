"""``step_temp_hbm_gb``: see ``perf.memory_shares.step_temp_hbm_gb``."""

from perf.memory_shares import step_temp_hbm_gb as read  # noqa: F401
