"""The OLMoE rehearsal configuration's reference, found by name under
``tests/perf``: the shipped reference's file (``perf/references/olmoe.py``),
loaded and not copied, at the rehearsal's 2 experts a token (what the
parameter tree does not carry is a constant of the file)."""

from __future__ import annotations

import importlib.util
import os

_SHIPPED = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "perf", "references", "olmoe.py"
)
_spec = importlib.util.spec_from_file_location("perf_references_shipped_olmoe", _SHIPPED)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
_module.EXPERTS_PER_TOKEN = 2

loss_and_grads = _module.loss_and_grads
