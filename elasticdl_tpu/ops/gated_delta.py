"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) as a
chunked scan: two Pallas kernels that carry a head's state over a sequence's
chunks, ``ops/ssd.py``'s sibling (docs/designs/gated_delta_rule.md).

The recurrence, a value head (``k_t``, ``q_t``: ``dk`` keys of the head's key
head; ``v_t``: ``dv`` values; ``g_t <= 0`` the log of the decay and ``beta_t``
in (0, 1), scalars a value head)::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T k_t)^T
    o_t = S_t^T q_t                                   S: (dk, dv)

Unlike the selective scan's, the update reads the state it writes (the
rank-one correction), so a chunk of ``C`` steps is first solved for what each
step really writes.  With ``gamma`` the running sum of ``g`` inside a chunk,
``K_b = beta o K``, ``V_b = beta o V`` and ``S`` the chunk's starting state::

    A  = strict_lower((K_b K^T) o exp(gamma_i - gamma_j))      (C, C)
    T  = (I + A)^{-1}                                          unit lower
    W  = T (K_b o exp(gamma)),   U = T V_b
    V' = U - W S
    O  = (Q o exp(gamma)) S + ((Q K^T) o exp(gamma_i - gamma_j), j <= i) V'
    S' = exp(gamma_C) S + (K o exp(gamma_C - gamma))^T V'

Every decay is a difference of running sums taken before the exponential, in
float32, as ``ops/ssd.py`` does.  Products take their operands in the inputs'
dtype and accumulate in float32; ``A``, ``T`` and the carried state are
float32.

**The solve** (:func:`_unit_lower_inverse`): the 16 x 16 diagonal blocks of
``I + A`` are inverted by the finite product ``(I - N)^{-1} = (I + N)(I +
N^2)(I + N^4)(I + N^8)`` of the nilpotent ``N`` (the blocks side by side in one
block-diagonal matrix: six products), and the blocks below the diagonal by the
same product one level up: ``I + A = (I + D)(I + T_D L)`` with ``L`` the
strictly block-lower part, ``M = -T_D L`` nilpotent over the ``C / 16``
blocks, ``T = (I + M)(I + M^2)... T_D``.  Ten ``C x C`` products at ``C`` =
64, in float32 (three passes of the inputs' halves, :func:`_dot3`).  The one
product form over the whole chunk, which the same count would buy, lets the
powers of ``N`` grow by binomials of 63 before they cancel where a chunk's
keys are alike; blocks of 16 bound them by binomials of 15, and the level
above multiplies true inverses.

**Layout.**  ``q`` and ``k`` are ``(batch, T, key heads x dk)`` and ``v``,
``o`` ``(batch, T, value heads x dv)``, as the layer's projections leave
them; the grid is (batch, key head, chunk), the chunks of a sequence in
order, and a step works the value heads its key head serves
(``repeat_interleave``: value head ``h`` reads key head ``h // (Hv // Hk)``),
sharing ``K K^T`` and ``Q K^T`` between them.  ``gamma`` and ``beta`` come as
float32 rows a value head and chunk.

``gdn_fwd`` also writes the state each chunk started from; ``gdn_bwd`` walks
the chunks in reverse carrying ``dS`` and recomputes ``A``, ``T``, ``W``,
``U`` and ``V'`` from them.  It returns ``dq``, ``dk``, ``dv`` and, as
float32 rows, ``beta``'s gradient and ``gamma``'s; the running sums and
everything before them (``g`` from ``A_log``, ``dt_bias`` and the projection)
are plain ``jax.numpy`` around the kernels, differentiated by JAX.

**Shapes the kernels take** (:func:`scan_tile`): ``dk`` and ``dv`` whole lane
tiles, a chunk of 16 x a power of two.  Any other shape runs
:func:`_chunked_plain`, the same mathematics in ``jax.numpy`` with ``T`` by a
triangular solve, which the kernels are tested against and which JAX
differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import on_mesh
# the sibling scan's helpers: a product rounded once to a dtype, a row as a
# column, a column widened to a block's lanes
from elasticdl_tpu.ops.ssd import _NT, _TN, _column, _dot, _fit

GDN_FWD = "gdn_fwd"
GDN_BWD = "gdn_bwd"

_LANES = 128
_BLOCK = 16  # the diagonal blocks the solve inverts first
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)
_f32 = jnp.float32


def scan_tile(key_width, value_width, chunk) -> bool:
    """Whether the kernels tile heads of ``key_width`` keys and
    ``value_width`` values in chunks of ``chunk`` steps."""
    blocks = chunk // _BLOCK
    return (
        key_width % _LANES == 0 and value_width % _LANES == 0
        and chunk % _BLOCK == 0 and blocks & (blocks - 1) == 0
    )


def _dot3(a, b):
    """A float32 product as three passes of bfloat16 halves (``a_hi b_hi +
    a_hi b_lo + a_lo b_hi``: what is dropped is 2^-16 of a term), float32
    accumulation: the matrix unit takes no float32 operand."""
    bf16 = jnp.bfloat16
    a_hi, b_hi = a.astype(bf16), b.astype(bf16)
    a_lo = (a - a_hi.astype(_f32)).astype(bf16)
    b_lo = (b - b_hi.astype(_f32)).astype(bf16)
    dot = functools.partial(jax.lax.dot, preferred_element_type=_f32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _iotas(length):
    shape = (length, length)
    return (
        jax.lax.broadcasted_iota(jnp.int32, shape, 0),
        jax.lax.broadcasted_iota(jnp.int32, shape, 1),
    )


def _unit_lower_inverse(a):
    """``(I + a)^{-1}`` of a strictly lower-triangular float32 ``a`` (C, C),
    by the module's two levels of finite products."""
    length = a.shape[0]
    row, col = _iotas(length)
    eye = (row == col).astype(_f32)
    diagonal = jnp.where(row // _BLOCK == col // _BLOCK, a, 0.0)
    power = -diagonal
    inverse = eye + power
    width = 2
    while width < min(_BLOCK, length):
        power = _dot3(power, power)
        inverse = inverse + _dot3(inverse, power)
        width *= 2
    if length <= _BLOCK:
        return inverse
    power = -_dot3(inverse, a - diagonal)
    above = eye + power
    width = 2 * _BLOCK
    while width < length:
        power = _dot3(power, power)
        above = above + _dot3(above, power)
        width *= 2
    return _dot3(above, inverse)


def _row_sums(x):
    """``sum_j x[i, j]`` as a ``(1, rows)`` row."""
    return jnp.sum(x.T, axis=0, keepdims=True)


def _total(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


class _Head:
    """What both kernels make of one value head of a chunk: the decays, the
    solve and what it gives, from the key head's ``q``, ``k`` and their two
    score matrices, the head's ``v``, its rows ``gamma`` and ``beta`` and the
    state the chunk starts from."""

    def __init__(self, q, k, v, keys, scores, gamma, beta, state):
        length, dk = k.shape
        dv = v.shape[1]
        dtype = k.dtype
        self.dot = dot = functools.partial(_dot, dtype=dtype)
        row, col = _iotas(length)
        column = _column(gamma)
        self.lower, self.strict = row >= col, row > col
        self.decay = jnp.where(
            self.lower, jnp.exp(jnp.minimum(column - gamma, 0.0)), 0.0
        )
        self.beta = _column(beta)
        self.keys = keys
        self.a = jnp.where(self.strict, self.beta * keys * self.decay, 0.0)
        self.t = _unit_lower_inverse(self.a)
        self.grown = jnp.exp(_fit(column, dk))  # exp(gamma), a column
        # exp(gamma_C), a row across the chunk's steps and across the values
        self.last_steps = jnp.exp(column[-1:, :])
        self.last = _fit(self.last_steps, dv)
        # exp(gamma_C - gamma), a column
        self.left = jnp.exp(_fit(column[-1:, :], dk) - _fit(column, dk))
        self.beta_k, self.beta_v = _fit(self.beta, dk), _fit(self.beta, dv)
        kf, self.vf = k.astype(_f32), v.astype(_f32)
        self.kf, self.qf = kf, q.astype(_f32)
        # K_b o e^gamma, Q o e^gamma and K o e^(gamma_C - gamma): float32
        # for gamma's gradient, rounded once for the products
        self.k_decayed_f = kf * self.beta_k * self.grown
        self.q_grown_f = self.qf * self.grown
        self.k_left_f = kf * self.left
        self.k_decayed = self.k_decayed_f.astype(dtype)
        self.v_beta = (self.vf * self.beta_v).astype(dtype)
        self.w = dot(self.t, self.k_decayed)
        u = dot(self.t, self.v_beta)
        self.state = state
        self.v_new = u - dot(self.w, state)
        self.q_grown = self.q_grown_f.astype(dtype)
        self.k_left = self.k_left_f.astype(dtype)
        self.within = scores * self.decay  # (Q K^T) o decays, j <= i

    def out(self):
        return self.dot(self.q_grown, self.state) + self.dot(
            self.within, self.v_new
        )

    def handed(self):
        return self.last * self.state + self.dot(
            self.k_left, self.v_new, _TN
        )


def _fwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref, o_ref, start_ref, state, *, dv
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, _f32)

    q, k = q_ref[...], k_ref[...]
    dot = functools.partial(_dot, dtype=k.dtype)
    keys, scores = dot(k, k, _NT), dot(q, k, _NT)
    for u in range(gamma_ref.shape[0]):
        lanes = slice(u * dv, (u + 1) * dv)
        s = state[u]
        start_ref[u] = s
        head = _Head(
            q, k, v_ref[:, lanes], keys, scores,
            gamma_ref[u:u + 1, :], beta_ref[u:u + 1, :], s,
        )
        o_ref[:, lanes] = head.out().astype(o_ref.dtype)
        state[u] = head.handed()


def _bwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref, do_ref, start_ref,
    dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref, d_state, *, dv,
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros(d_state.shape, _f32)

    q, k = q_ref[...], k_ref[...]
    length = k.shape[0]
    dot = functools.partial(_dot, dtype=k.dtype)
    keys, scores = dot(k, k, _NT), dot(q, k, _NT)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, length), 1) == length - 1
    dq = jnp.zeros(q.shape, _f32)
    dk = jnp.zeros(k.shape, _f32)
    for u in range(gamma_ref.shape[0]):
        lanes = slice(u * dv, (u + 1) * dv)
        s, ds = start_ref[u], d_state[u]
        h = _Head(
            q, k, v_ref[:, lanes], keys, scores,
            gamma_ref[u:u + 1, :], beta_ref[u:u + 1, :], s,
        )
        do = do_ref[:, lanes]
        # O = (Q o e) S + P V',  S' = e_C S + K_left^T V'
        d_v_new = dot(h.within, do, _TN) + dot(h.k_left, ds)
        d_within = jnp.where(h.lower, dot(do, h.v_new, _NT), 0.0)
        d_scores = d_within * h.decay
        d_q_grown = dot(do, s, _NT)
        dq = dq + dot(d_scores, k) + d_q_grown * h.grown
        dk = dk + dot(d_scores, q, _TN)
        by_pair = d_within * h.within
        d_gamma = (
            _row_sums(by_pair) - jnp.sum(by_pair, axis=0, keepdims=True)
            + _row_sums(d_q_grown * h.q_grown_f)
        )
        d_k_left = dot(h.v_new, ds, _NT)
        dk = dk + d_k_left * h.left
        through = _row_sums(d_k_left * h.k_left_f)
        d_gamma = d_gamma - through
        at_end = jnp.sum(
            through, axis=1, keepdims=True
        ) + h.last_steps * _total(s * ds)
        # V' = U - W S
        d_w = -dot(d_v_new, s, _NT)
        d_state[u] = (
            dot(h.q_grown, do, _TN) + h.last * ds - dot(h.w, d_v_new, _TN)
        )
        # W = T K_d, U = T V_b, T = (I + A)^{-1}
        d_t = dot(d_w, h.k_decayed, _NT) + dot(d_v_new, h.v_beta, _NT)
        d_k_decayed = dot(h.t, d_w, _TN)
        d_v_beta = dot(h.t, d_v_new, _TN)
        d_a = jnp.where(h.strict, -dot(dot(h.t, d_t, _TN), h.t, _NT), 0.0)
        by_pair = d_a * h.a
        d_gamma = (
            d_gamma + _row_sums(by_pair)
            - jnp.sum(by_pair, axis=0, keepdims=True)
            + _row_sums(d_k_decayed * h.k_decayed_f)
        )
        d_keys = d_a * h.beta * h.decay
        dk = (
            dk + dot(d_keys, k) + dot(d_keys, k, _TN)
            + d_k_decayed * h.beta_k * h.grown
        )
        d_beta = (
            _row_sums(d_a * h.keys * h.decay)
            + _row_sums(d_k_decayed * h.kf * h.grown)
            + _row_sums(d_v_beta * h.vf)
        )
        dv_ref[:, lanes] = (d_v_beta * h.beta_v).astype(dv_ref.dtype)
        dgamma_ref[u:u + 1, :] = d_gamma + jnp.where(at_last, at_end, 0.0)
        dbeta_ref[u:u + 1, :] = d_beta
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)


class _Plan:
    """The block specs of one call over the grid (batch, key head, step);
    ``chunk_of(step)`` is the chunk a step works on."""

    def __init__(self, q, v, rows, chunk_of):
        self.batch, self.chunks, self.heads, self.per, length = rows.shape
        self.dk = q.shape[2] // self.heads
        self.dv = v.shape[2] // (self.heads * self.per)
        self.grid = (self.batch, self.heads, self.chunks)

        def window(width):
            return pl.BlockSpec(
                (None, length, width), lambda i, h, j: (i, chunk_of(j), h)
            )

        self.keys = window(self.dk)
        self.values = window(self.per * self.dv)
        self.rows = pl.BlockSpec(
            (None, None, None, self.per, length),
            lambda i, h, j: (i, chunk_of(j), h, 0, 0),
        )
        self.starts = pl.BlockSpec(
            (None, None, None, self.per, self.dk, self.dv),
            lambda i, h, j: (i, chunk_of(j), h, 0, 0, 0),
        )
        self.start_shape = jax.ShapeDtypeStruct(
            (*rows.shape[:4], self.dk, self.dv), _f32
        )
        self.state = pltpu.VMEM((self.per, self.dk, self.dv), _f32)


def _forward(q, k, v, gamma, beta, interpret):
    """``q``, ``k`` (batch, T, Hk dk); ``v`` (batch, T, Hv dv); ``gamma``,
    ``beta`` (batch, chunks, Hk, Hv / Hk, C) float32."""
    plan = _Plan(q, v, gamma, lambda j: j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dv=plan.dv),
        grid=plan.grid,
        in_specs=[plan.keys, plan.keys, plan.values, plan.rows, plan.rows],
        out_specs=[plan.values, plan.starts],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype), plan.start_shape],
        scratch_shapes=[plan.state],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=GDN_FWD,
    )(q, k, v, gamma, beta)


def _backward(q, k, v, gamma, beta, do, start, interpret):
    chunks = gamma.shape[1]
    plan = _Plan(q, v, gamma, lambda j: chunks - 1 - j)
    rows = jax.ShapeDtypeStruct(gamma.shape, _f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dv=plan.dv),
        grid=plan.grid,
        in_specs=[
            plan.keys, plan.keys, plan.values, plan.rows, plan.rows,
            plan.values, plan.starts,
        ],
        out_specs=[plan.keys, plan.keys, plan.values, plan.rows, plan.rows],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            rows, rows,
        ],
        scratch_shapes=[plan.state],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=GDN_BWD,
    )(q, k, v, gamma, beta, do, start)


def _rows_by_head(steps, heads, length):
    """``(batch, T, Hv)`` float32 as the kernels read it, lane-dense rows:
    ``(batch, chunks, Hk, Hv / Hk, C)``."""
    batch, count, values = steps.shape
    return steps.reshape(
        batch, count // length, length, heads, values // heads
    ).transpose(0, 1, 3, 4, 2)


def _steps_by_head(rows):
    """:func:`_rows_by_head` undone."""
    batch, chunks, heads, per, length = rows.shape
    return rows.transpose(0, 1, 4, 2, 3).reshape(
        batch, chunks * length, heads * per
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gdn_core(q, k, v, gamma, beta, heads, chunk, interpret):
    """``q``, ``k`` (batch, T, Hk dk), ``v`` and the result (batch, T, Hv
    dv); ``gamma``, ``beta`` (batch, T, Hv) float32.  ``heads``: Hk.  The
    shape tiles (:func:`scan_tile`)."""
    return _gdn_core_fwd(q, k, v, gamma, beta, heads, chunk, interpret)[0]


def _gdn_core_fwd(q, k, v, gamma, beta, heads, chunk, interpret):
    rows = [_rows_by_head(x, heads, chunk) for x in (gamma, beta)]
    o, start = _forward(q, k, v, *rows, interpret)
    return o, (q, k, v, gamma, beta, start)


def _gdn_core_bwd(heads, chunk, interpret, residuals, do):
    q, k, v, gamma, beta, start = residuals
    rows = [_rows_by_head(x, heads, chunk) for x in (gamma, beta)]
    dq, dk, dv, d_gamma, d_beta = _backward(
        q, k, v, *rows, do.astype(v.dtype), start, interpret
    )
    return dq, dk, dv, _steps_by_head(d_gamma), _steps_by_head(d_beta)


_gdn_core.defvjp(_gdn_core_fwd, _gdn_core_bwd)


def _chunked_plain(q, k, v, gamma, beta, heads, chunk):
    """:func:`_gdn_core` in ``jax.numpy`` with the kernels' precisions and
    rounding points, ``T`` by a triangular solve; JAX differentiates it."""
    batch, steps, _ = q.shape
    values = gamma.shape[2]
    per = values // heads
    dtype = k.dtype
    product = functools.partial(jnp.einsum, preferred_element_type=_f32)

    def chunked(x, *shape):
        return jnp.moveaxis(
            x.reshape(batch, steps // chunk, chunk, *shape), 2, -2
        )

    # [batch, chunk, key head, (value head of it,) step, width]
    q, k = (chunked(x, heads, -1) for x in (q, k))
    v = chunked(v, heads, per, -1)
    gamma, beta = (
        jnp.moveaxis(
            x.reshape(batch, steps // chunk, chunk, heads, per), 2, -1
        )
        for x in (gamma, beta)
    )
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(
        lower,
        jnp.exp(jnp.minimum(gamma[..., :, None] - gamma[..., None, :], 0.0)),
        0.0,
    )
    keys = product("bchik,bchjk->bchij", k, k)[:, :, :, None]
    scores = product("bchik,bchjk->bchij", q, k)[:, :, :, None]
    a = jnp.where(lower & ~jnp.eye(chunk, dtype=bool),
                  beta[..., None] * keys * decay, 0.0)
    eye = jnp.eye(chunk, dtype=_f32)
    t = jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True,
    )
    kf, qf = (x.astype(_f32)[:, :, :, None] for x in (k, q))
    grown = jnp.exp(gamma)[..., None]
    left = jnp.exp(gamma[..., -1:] - gamma)[..., None]
    last = jnp.exp(gamma[..., -1])[..., None, None]
    k_decayed = (kf * beta[..., None] * grown).astype(dtype)
    v_beta = (v.astype(_f32) * beta[..., None]).astype(dtype)
    t = t.astype(dtype)
    w = product("bchuij,bchujk->bchuik", t, k_decayed).astype(dtype)
    u = product("bchuij,bchujv->bchuiv", t, v_beta)
    q_grown = (qf * grown).astype(dtype)
    k_left = (kf * left).astype(dtype)
    within = (scores * decay).astype(dtype)

    def carry(state, chunk_of):
        w, u, q_grown, within, k_left, last = chunk_of
        s = state.astype(dtype)
        v_new = (u - product("bhuik,bhukv->bhuiv", w, s)).astype(dtype)
        out = product("bhuik,bhukv->bhuiv", q_grown, s) + product(
            "bhuij,bhujv->bhuiv", within, v_new
        )
        state = last * state + product("bhuik,bhuiv->bhukv", k_left, v_new)
        return state, out

    start = jnp.zeros((batch, heads, per, q.shape[-1], v.shape[-1]), _f32)
    _, out = jax.lax.scan(
        carry, start,
        tuple(
            jnp.moveaxis(x, 1, 0)
            for x in (w, u, q_grown, within, k_left, last)
        ),
    )
    # [chunk, batch, key head, value head, step, dv] -> (batch, T, Hv dv)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(batch, steps, -1).astype(
        dtype
    )


def gated_delta_chunked(
    q, k, v, g, beta, *, chunk: int = 128, interpret: bool | None = None
):
    """The gated delta rule on one device.  ``q``, ``k`` (batch, T, Hk, dk),
    already normalised and scaled as the layer wants them; ``v`` (batch, T,
    Hv, dv) with ``Hv`` a multiple of ``Hk``; ``g`` (batch, T, Hv) float32,
    the decay's log, <= 0; ``beta`` (batch, T, Hv) float32.  Returns ``o``
    (batch, T, Hv, dv).  ``T`` that is no whole number of chunks is padded
    with steps of ``g = 0``, ``beta = 0`` and ``k = 0``, which decay nothing
    and write nothing.  Differentiable in every argument.  ``interpret=None``
    follows the default backend."""
    if interpret is None:
        interpret = on_mesh.default_interpret()
    batch, steps, heads, dk = k.shape
    values, dv = v.shape[2:]
    if values % heads or q.shape != k.shape or g.shape != (batch, steps, values):
        raise ValueError(
            f"{values} value heads over {heads} key heads, q {q.shape} beside "
            f"k {k.shape}, g {g.shape}"
        )
    pad = -steps % chunk
    flat = [x.reshape(batch, steps, -1) for x in (q, k, v)]
    g, beta = g.astype(_f32), beta.astype(_f32)
    if pad:
        *flat, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad), (0, 0)]) for x in (*flat, g, beta)
        )
    # the running sums inside each chunk as a product with a triangle of
    # ones (ops/ssd.py: XLA's cumsum is a reduce-window that is slower)
    gamma = jnp.einsum(
        "ts,bcsh->bcth", jnp.tril(jnp.ones((chunk, chunk), _f32)),
        g.reshape(batch, -1, chunk, values),
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(g.shape)
    if scan_tile(dk, dv, chunk):
        o = _gdn_core(*flat, gamma, beta, heads, chunk, interpret)
    else:
        o = _chunked_plain(*flat, gamma, beta, heads, chunk)
    return o[:, :steps].reshape(batch, steps, values, dv)


def gated_delta_scan(q, k, v, g, beta, *, chunk: int = 128):
    """:func:`gated_delta_chunked` under the registered mesh, mapped over the
    batch (``ops/on_mesh.py::over_batch``)."""
    return on_mesh.over_batch(
        lambda q, k, v, g, beta, interpret: gated_delta_chunked(
            q, k, v, g, beta, chunk=chunk, interpret=interpret
        ),
        (q, k, v, g, beta), (),
    )
