"""The rehearsal configuration's reference, found by name under
``tests/perf`` as a later configuration's is found under its own path.  The
mathematics is the shipped LM reference's, loaded from its file and not
copied: at the rehearsal's context (no multiple of its ``QUERY_BLOCK``) it
holds the whole score matrix and the whole logits at once."""

from __future__ import annotations

import importlib.util
import os

_SHIPPED = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "perf", "references",
    "transformer_lm.py",
)
_spec = importlib.util.spec_from_file_location("perf_references_shipped_lm", _SHIPPED)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)

loss_and_grads = _module.loss_and_grads
