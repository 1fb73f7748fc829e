"""Parameter/batch sharding rules.

Where the reference decides "which PS pod owns this variable" by name hash
(``hash_utils.py:4``, ``worker.py:371-381``), the TPU build decides "which
mesh axes shard this array" by *rules over parameter paths*: an ordered
list of ``(path_regex, PartitionSpec)`` pairs, first match wins, default
replicated.  Layers can also attach explicit specs via flax metadata;
rules are the policy layer on top.

FSDP: with an ``fsdp`` axis of size > 1, parameters without an explicit
rule are sharded along their largest divisible dimension — the standard
ZeRO-3-style layout where each dp rank owns a parameter slice and XLA
all-gathers just-in-time.
"""

from __future__ import annotations

import re
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.utils.constants import MeshAxis
from elasticdl_tpu.utils.tree_utils import _key_str


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 0, sp_dim: int | None = None) -> NamedSharding:
    """Leading-dim batch sharding over dp(+fsdp); optionally shard a
    sequence dimension over sp."""
    from elasticdl_tpu.parallel.mesh import data_parallel_axes

    axes = data_parallel_axes(mesh)
    spec = [axes if axes else None]
    if ndim:
        rest = [None] * (ndim - 1)
        if (
            sp_dim is not None
            and MeshAxis.SP in mesh.axis_names
            and mesh.shape[MeshAxis.SP] > 1
        ):
            rest[sp_dim - 1] = MeshAxis.SP
        spec.extend(rest)
    return NamedSharding(mesh, P(*spec))


class Rule:
    def __init__(self, pattern: str, spec: P):
        self.regex = re.compile(pattern)
        self.spec = spec

    def matches(self, path: str) -> bool:
        return self.regex.search(path) is not None


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([_axis_size(mesh, a) for a in axis]))
    return mesh.shape[axis] if axis in mesh.axis_names else 0


def _spec_fits(spec: P, shape, mesh: Mesh) -> bool:
    for dim, axis in enumerate(spec):
        if axis is None:
            continue
        size = _axis_size(mesh, axis)
        if size == 0 or dim >= len(shape) or shape[dim] % size != 0:
            return False
    return True


def _fsdp_spec(shape, mesh: Mesh) -> P:
    """Shard the largest divisible dim over fsdp; replicate if none fits."""
    size = mesh.shape.get(MeshAxis.FSDP, 1)
    if size <= 1 or not shape:
        return P()
    dims = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in dims:
        if shape[d] % size == 0 and shape[d] >= size:
            spec = [None] * len(shape)
            spec[d] = MeshAxis.FSDP
            return P(*spec)
    return P()


def infer_param_specs(
    params,
    mesh: Mesh,
    rules: Sequence[Rule] = (),
) -> dict:
    """PartitionSpec pytree for ``params``: first matching rule wins (if it
    fits the shape), then FSDP auto-sharding, else replicated."""

    def _spec_for(path_entries, leaf):
        path = "/".join(_key_str(k) for k in path_entries)
        shape = np.shape(leaf)
        for rule in rules:
            if rule.matches(path):
                if _spec_fits(rule.spec, shape, mesh):
                    return rule.spec
                break
        return _fsdp_spec(shape, mesh)

    return jax.tree_util.tree_map_with_path(_spec_for, params)


def specs_to_shardings(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def place_tree(tree, shardings):
    """Device-put a pytree with per-leaf shardings."""
    return jax.tree_util.tree_map(
        lambda leaf, sh: jax.device_put(leaf, sh), tree, shardings
    )


# Default tensor-parallel rules for transformer-style parameter names.
# (flax puts weights under e.g. ".../attention/query/kernel"); column- vs
# row-parallel follows the Megatron convention so only one psum per block
# is needed — XLA derives it from these shardings.
def default_tp_rules() -> list[Rule]:
    tp = MeshAxis.TP
    return [
        Rule(r"(query|key|value|q_proj|k_proj|v_proj)/kernel$", P(None, tp)),
        Rule(r"(out|o_proj|attn_out)/kernel$", P(tp, None)),
        Rule(
            r"(mlp/up|mlp/gate|mlp_up|mlp_gate|fc1|intermediate)/kernel$",
            P(None, tp),
        ),
        Rule(r"(mlp/down|mlp_down|fc2|output)/kernel$", P(tp, None)),
        Rule(r"embedding/embedding$", P(tp, None)),
        Rule(r"(lm_head|logits)/kernel$", P(None, tp)),
    ]
