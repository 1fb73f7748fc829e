"""A compiled kernel on a mesh: the registry of the mesh the trainer runs on
and the one place that decides how a per-device function meets it.

A compiled Pallas kernel is an opaque custom call GSPMD cannot partition:
JAX refuses to lower it bare inside a multi-device jitted program
(interpreted, on the CPU, it is ordinary HLO and partitions, which hid this
from every test).  So every kernel family maps itself over the mesh, and
they all take the same three-rung decision (:func:`resolve`):

1. no mesh registered: call the function, kernels interpreted or compiled by
   the process's default backend;
2. a mesh of one device, or a caller's per-device region already open: call
   the function, kernels by the MESH's platform (a CPU mesh on a TPU-default
   machine, the virtual-device dry run, compiles for the CPU, where Pallas
   only runs interpreted);
3. else ``jax.shard_map`` over the mesh, by the caller's partition specs.

:func:`mapped` is that decision with the mapping; a caller supplies only
what is its own: the specs, and what crosses the region's boundary.
"""

from __future__ import annotations

import contextlib
import functools

import jax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops.ring_attention import sequence_shard_spec

# ---- mesh context (set by the trainer, read by layers) ---------------------

# process-global, NOT thread-local: one mesh per worker process (the SPMD
# model), and jit tracing may happen on a different thread than trainer
# construction
_mesh_context: list = [None, "sp", "ring"]


_SP_IMPLS = ("ring", "ulysses")


def set_attention_mesh(mesh, sp_axis: str = "sp", sp_impl: str = "ring"):
    """Register the mesh the layers' kernels run on (the name is the first
    user's: attention's sequence parallelism).  A ``None`` mesh (or an
    ``sp`` axis of size 1) makes ``ops.attention.attention`` run the local
    kernel and lets GSPMD handle any sharding.  ``sp_impl`` picks the
    sequence-parallel algorithm: ``"ring"`` (K/V rotation; any head count)
    or ``"ulysses"`` (head/sequence all-to-all; needs heads % sp == 0).
    SPMDTrainer scopes this around every step call via
    :func:`attention_mesh_scope` — two trainers with different meshes in one
    process (bench, dryrun) must not see each other's mesh at (re)trace
    time."""
    if sp_impl not in _SP_IMPLS:
        # a typo must not silently fall back to ring
        raise ValueError(
            f"unknown sp_impl {sp_impl!r}; valid: {_SP_IMPLS}"
        )
    _mesh_context[0] = mesh
    _mesh_context[1] = sp_axis
    _mesh_context[2] = sp_impl


def get_attention_mesh():
    return _mesh_context[0], _mesh_context[1], _mesh_context[2]


@contextlib.contextmanager
def attention_mesh_scope(mesh, sp_axis: str = "sp", sp_impl: str | None = None):
    """Set-and-restore the attention mesh: tracing inside the scope (jit
    retraces on new shapes happen at call time) reads this mesh.
    ``sp_impl=None`` preserves the currently selected implementation —
    SPMDTrainer's step scopes must not clobber a global
    ``set_attention_mesh(..., sp_impl="ulysses")`` choice."""
    prev = tuple(_mesh_context)
    set_attention_mesh(
        mesh, sp_axis, _mesh_context[2] if sp_impl is None else sp_impl
    )
    try:
        yield
    finally:
        _mesh_context[:] = prev


def kernel_interpret(platform: str) -> bool:
    """Whether the pallas kernels run INTERPRETED on ``platform``: the
    CPU has no Mosaic, so it interprets (tests run the same kernel code
    the chip compiles); a TPU compiles; any other platform is an error —
    never a silent trip through the interpreter."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(
        f"pallas flash attention runs compiled on 'tpu' and interpreted "
        f"on 'cpu'; got platform {platform!r}"
    )


def default_interpret() -> bool:
    """:func:`kernel_interpret` of the process's default backend: what a
    kernel's entry point takes where its caller said ``interpret=None``."""
    return kernel_interpret(jax.default_backend())


# ---- the decision, and the mapping -----------------------------------------


def resolve():
    """``(interpret, mesh)`` for a per-device function called here:
    whether its kernels run interpreted, and the mesh it has to be mapped
    over, None where it is called as it stands (the module's rungs 1 and 2)."""
    mesh, _, _ = get_attention_mesh()
    if mesh is None:
        return default_interpret(), None
    interpret = kernel_interpret(mesh.devices.flat[0].platform)
    if mesh.devices.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        # one device, or already inside a caller's per-device region
        return interpret, None
    return interpret, mesh


def mapped(local, operands, specs, crossing=None):
    """``local(*operands, interpret=...)`` under the registered mesh.

    ``specs``: ``(in_specs, out_specs)`` for ``jax.shard_map``, or a function
    of the mesh that returns them, called only where the call is mapped.
    ``crossing``: ``(enter, leave)``, the forms an array takes at the mapped
    region's boundary and inside it: the region is handed ``enter(x)`` of
    each operand and gives ``enter`` of the result, ``local`` sees
    ``leave`` of them.  Where nothing is mapped nothing is converted."""
    interpret, mesh = resolve()
    local = functools.partial(local, interpret=interpret)
    if mesh is None:
        return local(*operands)
    in_specs, out_specs = specs(mesh) if callable(specs) else specs
    if crossing is None:
        per_device = local
    else:
        enter, leave = crossing
        operands = [enter(x) for x in operands]

        def per_device(*operands):
            return enter(local(*(leave(x) for x in operands)))

    out = jax.shard_map(
        per_device, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(*operands)
    return out if crossing is None else leave(out)


def over_batch(local, batched, shared):
    """:func:`mapped` over the mesh's data-parallel axes alone: each of
    ``batched`` (and the result, which is laid out like the first of them)
    split along its first axis, a sequence whole on its device, each of
    ``shared`` whole on every device."""

    def specs(mesh):
        rows = sequence_shard_spec(mesh, None, batched[0].shape[0], 1)[0]
        by_row = [P(rows, *[None] * (v.ndim - 1)) for v in batched]
        return (*by_row, *[P(None)] * len(shared)), by_row[0]

    return mapped(local, (*batched, *shared), specs)
