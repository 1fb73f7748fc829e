"""Rotary positions as one Pallas pass that reads an array once and writes
it once (``layers/attention.py::rope`` holds the plain form and chooses
between the two by shape)::

    out = x * C + partner(x) * S                    a head, in float32

    rotate-half     partner: half a head away       C = [cos | cos]
                    roll(x, width / 2 lanes)        S = [-sin | sin]
    adjacent pairs  partner: the pair's other lane  C = each cos on its two lanes
                    roll by one lane, up or down    S = each sin on its two lanes,
                    by the lane's parity            -sin first

which is ``x1 * cos - x2 * sin`` and ``x2 * cos + x1 * sin`` product for product
and sum for sum, so the same bits: read in ``x``'s dtype, computed in float32,
written in ``x``'s dtype.  The partner is a static permutation of a head's own
lanes, so a head may be narrower than a lane tile (64 wide: latent
attention's one rotary key, the indexer's one key) and the rotating lanes may
be the tail of a wider head whose first ``skip`` lanes, whole lane tiles, are
copied through (latent attention's q: 128 + 64).  A head narrower than a
tile is taken only where it is its array's one head: SEVERAL 64-wide heads
(the indexer's queries, LFM2's q and k) the same body turned bit for bit too,
but folded their rows are half padding in HBM and the projection that must
write them lost more than the pass won, so :func:`rotate_tile` keeps the
plain form for them (``lfm2_24b_a2b_seq4096x4`` -1.4% in PR 62's chip runs:
PERF.md section 6, PR 63; docs/designs/rotary_kernel.md has the table).

**The layout is the point.**  Where heads are 128 wide the attention kernels
take ``(batch * heads, tokens, width)`` (``ops/attention.py::
_heads_per_block``), XLA writes a projection and the QK-norm after it straight
into that layout, and a ``pallas_call`` pins its operands to the row-major
layout of the shape it is handed.  So the kernel is handed ``(batch, heads,
tokens, width)``, the transpose of the layer's ``(batch, tokens, heads,
width)``, reads it folded and writes it folded, forward and backward: both
transposes around it are layouts XLA was assigning already, and the compiled
steps hold no copy of a q-sized array that they did not hold before.  (Handed
the projection's merged rows ``(batch, tokens, heads * width)`` instead, the
step of ``trinity_mini_seq16384`` grew 32 float32 copies of q and k: XLA kept
the projection folded and turned the norm's output into rows through a copy;
PERF.md section 6, PR 45.)  The same holds where a head is 192 wide: the
flash kernels take it folded at 192 | 128, so the whole head is handed
folded and the tail is rotated in place: no slice of q and no concatenation
is left for XLA (PERF.md section 6, PR 63).

A grid step is a tile of rows by a block of heads; the tables' block follows
the row tile alone and the heads are the grid's innermost dimension, so a
table block is fetched once a row tile.  The backward is the same body with
``S`` negated (either partner permutation is its own transpose, and it moves
``S`` onto ``-S``).  The ``custom_vjp``'s residuals are the positions alone:
the tables are made again where the backward wants them (XLA shares one pair
a step where the positions are the tokens' indices), never kept a layer.

**The rule.**  What turns a position into an angle is a rule (:func:`rates`):
a float is the power law's base, ``theta^(-i / half)``; a :class:`Yarn` blends
that with the same frequencies over ``factor`` by a ramp over the pairs and
scales cos and sin (docs/designs/yarn_rope.md).  A rule changes the tables and
nothing else: the kernel is the one kernel, and the ``custom_vjp`` carries the
rule where it carried the base, as a static argument.

The names below are the device trace's op names; none starts with ``flash_``,
``swa_``, ``dsa_``, ``expert_gmm`` or ``ssd_``, which ``perf/`` reads as those
kernels.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROPE_FWD = "rope_fwd"
ROPE_BWD = "rope_bwd"

_LANES = 128
# rows of a grid step, and the lanes of its heads together at most (TPU v5e,
# 1 x 16,384 x 32 : 4 heads of 128: docs/designs/rotary_kernel.md)
_ROWS = 512
_BLOCK_LANES = 1024

_f32 = jnp.float32


def rotate_tile(shape, skip: int = 0):
    """``(row tile, heads a block)`` the kernels take ``x`` of ``shape``
    (batch, tokens, heads, width) with, the first ``skip`` lanes of a head
    passing through, from what the call can see; None where the plain form
    stays: fewer rows than one tile (a decode step, a small model), lanes
    that pass through and are no whole lane tiles, rotating lanes that are
    neither whole lane tiles nor half a tile, several heads narrower than a
    tile (folded, their rows are half padding in HBM: the module's
    docstring; an array's one head of 64 is taken)."""
    if len(shape) != 4:
        return None
    _, rows, heads, width = shape
    turning = width - skip
    if rows < _ROWS or skip % _LANES:
        return None
    if turning % _LANES and turning != _LANES // 2:
        return None
    if width < _LANES and heads > 1:
        return None
    # a head's lanes in VMEM: whole tiles
    padded = -(-width // _LANES) * _LANES
    held = max(1, min(heads, _BLOCK_LANES // padded))
    while heads % held:
        held -= 1
    return _ROWS, held


class Yarn(NamedTuple):
    """YaRN (Peng et al. 2023, arXiv:2309.00071) as HF
    ``_compute_yarn_parameters`` reads a ``rope_parameters`` group of
    ``rope_type: yarn``: the pairs that turn more than ``beta_fast`` times
    over ``original_length`` positions keep ``theta``'s frequency, those that
    turn less than ``beta_slow`` times take it over ``factor``, a linear ramp
    over the pairs between; cos and sin times ``attention_factor`` (None:
    ``0.1 ln(factor) + 1``)."""

    theta: float
    factor: float
    original_length: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None


# a ``rope_type: yarn`` group's keys by :class:`Yarn`'s fields
_YARN_KEYS = {
    "theta": "rope_theta",
    "factor": "factor",
    "original_length": "original_max_position_embeddings",
    "beta_fast": "beta_fast",
    "beta_slow": "beta_slow",
    "attention_factor": "attention_factor",
}


def rule_of(parameters):
    """The rule a published ``rope_parameters`` group states (``rope_type``,
    ``rope_theta`` and, for ``yarn``, its five numbers, those it leaves out
    or null at :class:`Yarn`'s defaults)."""
    kind = parameters.get("rope_type", "default")
    if kind == "default":
        return float(parameters["rope_theta"])
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}; valid: default, yarn")
    return Yarn(**{
        field: float(parameters[key])
        for field, key in _YARN_KEYS.items()
        if parameters.get(key) is not None
    })


def scaled(rule, *arrays):
    """cos and sin times what the rule multiplies them by (a base: nothing,
    the arrays themselves)."""
    if not isinstance(rule, Yarn):
        return arrays
    by = rule.attention_factor
    if by is None:
        by = 0.1 * math.log(rule.factor) + 1.0
    return tuple(x * by for x in arrays)


def rates(rule, half: int):
    """The float32 angle a position turns pair ``i`` of the ``half`` pairs
    of a head by."""
    if not isinstance(rule, Yarn):
        return rule ** (-jnp.arange(half, dtype=_f32) / half)
    width = 2 * half

    def pair_turning(turns):
        # the (fractional) pair that turns ``turns`` times over the
        # original length
        return (
            width
            * math.log(rule.original_length / (turns * 2 * math.pi))
            / (2 * math.log(rule.theta))
        )

    low = max(math.floor(pair_turning(rule.beta_fast)), 0)
    high = min(math.ceil(pair_turning(rule.beta_slow)), width - 1)
    if low == high:
        high += 0.001  # HF: no division by zero
    pair = jnp.arange(half, dtype=_f32)
    ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    rate = rule.theta ** (-pair / half)
    return (1.0 - ramp) * rate + ramp * (rate / rule.factor)


def angles(positions, rule, half: int, sections=()):
    """Float32 ``positions * rate_i`` for the ``half`` pairs of a head
    (:func:`rates`; ``theta^(-i / half)`` where the rule is a base):
    ``(tokens, half)`` for ``positions`` (tokens,); ``(batch, tokens,
    half)`` for (batch, components, tokens), frequency ``i`` taking the
    component whose section it lies in, ``sections[c]`` frequencies each in
    order.  The one definition both forms of ``layers/attention.py::rope``
    turn by."""
    rate = rates(rule, half)
    if positions.ndim != 3:
        return positions.astype(_f32)[:, None] * rate[None, :]
    if sum(sections) != half:
        raise ValueError(
            f"mrope sections {sections} do not cover {half} frequencies"
        )
    component = jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections),
        total_repeat_length=half,
    )
    # (batch, tokens, half): each frequency's own component
    of_frequency = jnp.take(
        positions.astype(_f32), component, axis=1
    ).transpose(0, 2, 1)
    return of_frequency * rate


def tables(positions, rule, width: int, sections=(), interleave=False):
    """``(C, S)`` float32 over a head of ``width``, from :func:`angles`,
    :func:`scaled`: ``[cos | cos]`` and ``[-sin | sin]``, or with
    ``interleave`` each frequency on its pair's two lanes, ``-sin`` on the
    first."""
    turn = angles(positions, rule, width // 2, sections)
    cos, sin = scaled(rule, jnp.cos(turn), jnp.sin(turn))
    if interleave:
        return (
            jnp.repeat(cos, 2, axis=-1),
            jnp.stack([-sin, sin], axis=-1).reshape(*sin.shape[:-1], width),
        )
    return (
        jnp.concatenate([cos, cos], axis=-1),
        jnp.concatenate([-sin, sin], axis=-1),
    )


def _partner(x, interleave):
    """Each lane's partner in its place, ``x`` (rows, a head's rotating
    lanes): half a head away, or the other lane of its adjacent pair."""
    lanes = x.shape[1]
    if not interleave:
        return pltpu.roll(x, lanes // 2, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(
        lane % 2 == 0, pltpu.roll(x, lanes - 1, 1), pltpu.roll(x, 1, 1)
    )


def _rotate_kernel(
    x_ref, cos_ref, sin_ref, out_ref, *, interleave, skip, backward
):
    """Some heads of a tile of rows, ``(heads, rows, width)``.  The
    backward takes ``S`` negated: the partner permutation is its own
    transpose, and it moves ``S`` onto ``-S``."""
    cos, sin = cos_ref[...], sin_ref[...]
    # the whole head, or the lanes behind those that pass through
    lanes = (slice(None), slice(skip, None)) if skip else ()
    for head in range(x_ref.shape[0]):
        if skip:
            out_ref[head, :, :skip] = x_ref[head, :, :skip]
        x = x_ref[(head, *lanes)].astype(_f32)
        turned = _partner(x, interleave) * sin
        out = x * cos - turned if backward else x * cos + turned
        out_ref[(head, *lanes)] = out.astype(out_ref.dtype)


def _rotate(x, cos, sin, interpret, interleave=False, skip=0, backward=False):
    """The one ``pallas_call``, over ``x`` (batch, heads, tokens, width);
    the tables over the ``width - skip`` lanes that rotate."""
    batch, heads, rows, width = x.shape
    tile, held = rotate_tile((batch, rows, heads, width), skip)
    folded = pl.BlockSpec(
        (None, held, tile, width), lambda b, i, h: (b, h, i, 0)
    )
    turning = width - skip
    table = (
        pl.BlockSpec((None, tile, turning), lambda b, i, h: (b, i, 0))
        if cos.ndim == 3
        else pl.BlockSpec((tile, turning), lambda b, i, h: (i, 0))
    )
    return pl.pallas_call(
        functools.partial(
            _rotate_kernel, interleave=interleave, skip=skip,
            backward=backward,
        ),
        grid=(batch, pl.cdiv(rows, tile), heads // held),
        in_specs=[folded, table, table],
        out_specs=folded,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=ROPE_BWD if backward else ROPE_FWD,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def rotate(x, positions, rule, sections, interleave, skip, interpret):
    """Rotary positions on ``x`` (batch, heads, tokens, width), folded as
    the attention kernels take it, behind a head's first ``skip`` lanes;
    ``positions``, ``rule`` (hashable: a base or a :class:`Yarn`),
    ``sections`` and ``interleave`` as :func:`tables` takes them.  The shape
    must tile (:func:`rotate_tile`)."""
    cos, sin = tables(
        positions, rule, x.shape[3] - skip, sections, interleave
    )
    return _rotate(x, cos, sin, interpret, interleave, skip)


def _rotate_fwd(x, positions, rule, sections, interleave, skip, interpret):
    out = rotate(x, positions, rule, sections, interleave, skip, interpret)
    return out, positions


def _rotate_bwd(rule, sections, interleave, skip, interpret, positions, d_out):
    cos, sin = tables(
        positions, rule, d_out.shape[3] - skip, sections, interleave
    )
    d_x = _rotate(d_out, cos, sin, interpret, interleave, skip, backward=True)
    if jnp.issubdtype(positions.dtype, jnp.floating):
        return d_x, jnp.zeros_like(positions)
    return d_x, np.zeros(positions.shape, jax.dtypes.float0)


rotate.defvjp(_rotate_fwd, _rotate_bwd)
