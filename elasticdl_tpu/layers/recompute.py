"""``nn.remat`` for a layer whose recomputed pass is handed what its first
pass found.

``nn.remat`` traces a layer once: the first pass and the recomputed one are
two cuts of one program, so a value both need has one derivation in both.
A layer that searches for something small and exact (the sparse-attention
selection's threshold, two int32 a query) would rather search once and have
the recomputed pass check the answer.  :func:`remat_with_findings` traces
the layer twice instead: the first pass as it stands, collecting what the
layer :func:`offer` s; the recomputed pass (``jax.checkpoint`` of the same
layer, inside the backward rule) with those findings laid out for
:func:`found` to hand back in the order they were offered.  The findings
are the only residuals beside the layer's inputs, which ``nn.remat`` keeps
too.  A layer that offers nothing is recomputed as ``nn.remat`` would.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from flax import linen as nn
from flax.core import lift

# the passes this thread is tracing, innermost last: ``[offered, laid out]``
_tracing = threading.local()


def _passes() -> list:
    if not hasattr(_tracing, "passes"):
        _tracing.passes = []
    return _tracing.passes


@contextlib.contextmanager
def _pass(findings):
    entry = [[], None if findings is None else list(findings)]
    _passes().append(entry)
    try:
        yield entry[0]
    finally:
        _passes().pop()


def offer(finding) -> None:
    """Keep ``finding`` (a pytree of small arrays) for this layer's
    recomputed pass; nothing outside :func:`remat_with_findings`."""
    if _passes():
        _passes()[-1][0].append(finding)


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

        def run(findings, variable_groups, rng_groups, moving):
            rest = iter(moving)
            scope = scope_fn(variable_groups, rng_groups)
            with _pass(findings) as offered:
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
            return run(None, variable_groups, rng_groups, moving)[0]

        def first(variable_groups, rng_groups, moving):
            # the recomputed pass reads the layer's inputs as they were
            # stored; so does this one (XLA may otherwise hand a fused
            # consumer the producer's unrounded float32, and a finding made
            # on those bits does not verify on the stored ones)
            moving = jax.lax.optimization_barrier(moving)
            out, offered = run(None, variable_groups, rng_groups, moving)
            return out, (variable_groups, rng_groups, moving, offered)

        def backward(kept, cotangent):
            *operands, offered = kept
            # (its undifferentiated pass is dead code: nothing reads the
            # outputs; the recomputed one runs under jax.checkpoint's own
            # name and barrier)
            again = jax.checkpoint(lambda *xs: run(offered, *xs)[0])
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
