"""Test harness configuration.

Force JAX onto a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (SURVEY §4: the reference collapses the
process boundary but keeps the protocol objects real; we collapse the pod
slice into 8 host-platform devices but keep the mesh/sharding real).

Must run before the first ``import jax`` anywhere in the test session.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep XLA compile parallelism sane on small CI machines.
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Pin the CPU backend through the config API as well as the environment.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# A fresh checkout has no _native.so (git-ignored): build it before
# collection, so skip-if-not-built marks see the codec the training entry
# points would build anyway (None without g++: those tests then skip).
from elasticdl_tpu.data.recordio import build as _codec_build  # noqa: E402

_codec_build.build(quiet=True)


import pytest  # noqa: E402


@pytest.fixture
def plain_rope_shapes(monkeypatch):
    """The shapes ``layers/attention.py::rope_plain`` is traced for while
    the test runs, in order: what ``rope`` left to the plain form."""
    from elasticdl_tpu.layers import attention

    plain_form, shapes = attention.rope_plain, []

    def counted(x, *args, **kwargs):
        shapes.append(x.shape)
        return plain_form(x, *args, **kwargs)

    monkeypatch.setattr(attention, "rope_plain", counted)
    return shapes
