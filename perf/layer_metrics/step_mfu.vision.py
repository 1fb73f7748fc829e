"""``step_mfu.vision``: see ``perf.layer_readers.step_mfu``."""

from perf.layer_readers import step_mfu as read  # noqa: F401
