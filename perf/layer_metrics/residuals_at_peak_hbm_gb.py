"""``residuals_at_peak_hbm_gb``: see ``perf.memory_shares.residuals_at_peak_hbm_gb``."""

from perf.memory_shares import residuals_at_peak_hbm_gb as read  # noqa: F401
