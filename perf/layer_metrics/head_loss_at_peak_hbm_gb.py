"""``head_loss_at_peak_hbm_gb``: see ``perf.memory_shares.head_loss_at_peak_hbm_gb``."""

from perf.memory_shares import head_loss_at_peak_hbm_gb as read  # noqa: F401
