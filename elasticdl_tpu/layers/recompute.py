"""``nn.remat`` for a layer whose recomputed pass is handed what its first
pass found.

``nn.remat`` traces a layer once: the first pass and the recomputed one are
two cuts of one program, so a value both need has one derivation in both.
A layer that searches for something exact (the sparse-attention selection's
threshold, two int32 a query) would rather search once and have the
recomputed pass check the answer, and a layer whose backward pass holds
work that depends on nothing downstream of it (the indexer's loss: its
gradient is known in the first pass up to the scalar cotangent) would
rather do that work once, beside the value.  :func:`remat_with_findings`
traces the layer twice instead: the first pass as it stands, collecting
what the layer :func:`offer` s; the recomputed pass (``jax.checkpoint`` of
the same layer, inside the backward rule) with those findings laid out for
:func:`found` to hand back in the order they were offered.
:func:`offers_kept` tells the layer which of its passes it is in.  The
findings are the only residuals beside the layer's inputs, which
``nn.remat`` keeps too, and they are made before the layer's output is
handed on.  A layer that offers nothing is recomputed as ``nn.remat``
would.

What bounds a finding is memory, not kind: every layer's findings are
alive from its first pass to its backward pass, so all of them are alive
at the step's peak (the last layer's backward pass), and the benchmark
holds ``peak_hbm_gb`` to 1%.  The threshold is 128 KB a layer at 16,384
tokens; the indexer's parameter gradients are 9 MB; the same gradients one
stage earlier, to the indexer's three outputs, are 37 MB, and four layers
of those did not fit (docs/designs/sparse_attention.md).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from flax import linen as nn
from flax.core import lift

# the passes this thread is tracing, innermost last: ``[offered, laid out,
# whether the offers are kept]``
_tracing = threading.local()


def _passes() -> list:
    if not hasattr(_tracing, "passes"):
        _tracing.passes = []
    return _tracing.passes


@contextlib.contextmanager
def _pass(findings, kept):
    entry = [[], None if findings is None else list(findings), kept]
    _passes().append(entry)
    try:
        yield entry[0]
    finally:
        _passes().pop()


def offer(finding) -> None:
    """Keep ``finding`` (a pytree of arrays, alive until this layer's
    backward pass) for this layer's recomputed pass; nothing outside
    :func:`remat_with_findings`."""
    if _passes():
        _passes()[-1][0].append(finding)


def offers_kept() -> bool:
    """Whether what this pass offers reaches a recomputed pass: True in the
    first pass of a layer that is being differentiated (the forward rule),
    False in an undifferentiated pass, in a recomputed one and outside
    :func:`remat_with_findings`.  So a layer can do in its first pass what
    only a backward pass would pay for, and nowhere else."""
    return bool(_passes()) and _passes()[-1][2]


def found():
    """What the first pass offered at this place, inside a recomputed pass;
    else None."""
    if not _passes() or _passes()[-1][1] is None:
        return None
    return _passes()[-1][1].pop(0)


def _lifted(fn, static_argnums=()):
    def inner(scope_fn, repack_fn, variable_groups, rng_groups, *args):
        static = {i: args[i] for i in static_argnums}
        moving = tuple(a for i, a in enumerate(args) if i not in static)

        def run(findings, kept, variable_groups, rng_groups, moving):
            rest = iter(moving)
            scope = scope_fn(variable_groups, rng_groups)
            with _pass(findings, kept) as offered:
                y = fn(
                    scope,
                    *(
                        static[i] if i in static else next(rest)
                        for i in range(len(args))
                    ),
                )
            return (y, repack_fn(scope)), tuple(offered)

        @jax.custom_vjp
        def layer(variable_groups, rng_groups, moving):
            return run(None, False, variable_groups, rng_groups, moving)[0]

        def first(variable_groups, rng_groups, moving):
            # the recomputed pass reads the layer's inputs as they were
            # stored; so does this one (XLA may otherwise hand a fused
            # consumer the producer's unrounded float32, and a finding made
            # on those bits does not verify on the stored ones)
            moving = jax.lax.optimization_barrier(moving)
            out, offered = run(
                None, True, variable_groups, rng_groups, moving
            )
            # a finding is made by the time the layer's output is: nothing
            # reads it before the backward pass, and XLA would put the work
            # behind it off until then with its operands alive meanwhile
            out, offered = jax.lax.optimization_barrier((out, offered))
            return out, (variable_groups, rng_groups, moving, offered)

        def backward(kept, cotangent):
            *operands, offered = kept
            # (its undifferentiated pass is dead code: nothing reads the
            # outputs; the recomputed one runs under jax.checkpoint's own
            # name and barrier)
            again = jax.checkpoint(lambda *xs: run(offered, False, *xs)[0])
            return jax.vjp(again, *operands)[1](cotangent)

        layer.defvjp(first, backward)
        return layer(variable_groups, rng_groups, moving)

    return lift.pack(
        inner, (True,), (True,), (True,), name="remat", enable_kwargs=False
    )


def remat_with_findings(module_class, static_argnums=()):
    """``nn.remat(module_class, static_argnums=...)`` whose recomputed pass
    sees the first pass's :func:`offer` s through :func:`found`."""
    return nn.transforms.lift_transform(
        _lifted, module_class,
        static_argnums=tuple(i - 1 for i in static_argnums),  # no ``self``
    )
