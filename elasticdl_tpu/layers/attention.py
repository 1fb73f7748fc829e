"""Flax attention layer over the framework kernels.

``MultiHeadSelfAttention`` projects QKV and dispatches through
:func:`elasticdl_tpu.ops.attention`: ring attention when the trainer's
mesh has an ``sp`` axis > 1 (sequence sharded across devices), else the
pallas flash kernel.  The layer itself is sharding-agnostic — GSPMD lays
out the projections; only the attention inner product needs the explicit
ring schedule.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

import elasticdl_tpu.ops.attention as attention_ops
from elasticdl_tpu.ops import on_mesh
from elasticdl_tpu.ops import rotary as rotary_ops


# the indexer's four submodules (``MultiHeadSelfAttention._indexer``): the
# parameters the indexer's loss trains, and no other loss
_INDEXER_PARAMS = (
    "index_query", "index_key", "index_key_norm", "index_weights"
)


def _kernels_interpret() -> bool:
    """Whether the sparse-attention kernels run interpreted, as
    ``ops/on_mesh.py`` decides it for every kernel; where it would map the
    call over a mesh, these kernels have no mapping to give it."""
    interpret, mesh = on_mesh.resolve()
    if mesh is not None:
        raise NotImplementedError(
            "sparse attention across devices is not built: one chip a "
            "sequence (docs/designs/sparse_attention.md)"
        )
    return interpret


class HeadsDense(nn.Module):
    """A projection into heads, or out of them over ``axis=(-2, -1)``, that
    owns ``nn.DenseGeneral``'s parameters (names, shapes and seeded initial
    values: ``kernel`` (embed, heads, width) or (heads, width, embed)) and
    computes ONE 2-D product over merged dimensions, bias included, before
    the result is given its heads.  ``MultiHeadSelfAttention`` takes it
    where the flash kernels read heads out of (batch, tokens, heads * width)
    rows (``ops.attention.flash_layout`` says ``"lanes"``: 64-wide heads).

    ``nn.DenseGeneral`` adds its bias to the 4-D result, and XLA's TPU
    layout assignment gives a (batch, tokens, heads, 64) intermediate a
    tokens-minor layout (a 64-wide minor dimension pads to 128 lanes), so
    every operand of the kernels crossed a layout-changing copy, eight a
    layer at 8 x 1,024 x 12 x 64, whatever the kernels read (PERF.md
    section 6, PR 36).  With nothing between the product and the reshape,
    the reshape in and the kernels' reshape back cancel.  Where the kernels
    take folded heads (width 128) ``nn.DenseGeneral`` stays: XLA writes its
    result straight into the folded layout, and the step compiles to the
    program it was before this class existed."""

    features: Any
    axis: Any = -1
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        features = (
            (self.features,)
            if isinstance(self.features, int)
            else tuple(self.features)
        )
        contracted = 1 if isinstance(self.axis, int) else len(self.axis)
        lead, inputs = x.shape[:-contracted], x.shape[-contracted:]
        flat = (math.prod(inputs), math.prod(features))

        def kernel_init(rng, shape, dtype):
            # nn.DenseGeneral's: drawn at the flat shape
            return nn.initializers.lecun_normal()(rng, flat, dtype).reshape(
                shape
            )

        kernel = self.param(
            "kernel", kernel_init, inputs + features, jnp.float32
        )
        bias = (
            self.param(
                "bias", nn.initializers.zeros_init(), features, jnp.float32
            )
            if self.use_bias
            else None
        )
        x, kernel, bias = nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype
        )
        y = x.reshape(lead + flat[:1]) @ kernel.reshape(flat)
        if bias is not None:
            y = y + bias.reshape(flat[1:])
        return y.reshape(lead + features)


class MultiHeadSelfAttention(nn.Module):
    num_heads: int
    causal: bool = False
    # grouped-query attention: fewer K/V heads than Q heads (0 = equal);
    # shrinks the KV projection + cache by num_heads/num_kv_heads
    num_kv_heads: int = 0
    # autoregressive decoding: keep a KV cache in the "cache" variable
    # collection (apply with mutable=["cache"]); each call appends one
    # step's K/V and attends over the filled prefix
    decode: bool = False
    max_decode_len: int = 0
    # compute dtype (e.g. bf16): projections and the attention kernel run
    # in it; parameters stay in param_dtype (f32) — mixed precision
    dtype: Any = None
    use_bias: bool = True
    # the rule the rotary positions turn q and k by (``rope``): a base > 0,
    # the power law's theta, or an ``ops/rotary.py::Yarn``; 0: none, the
    # model adds its positions at the embedding
    rope_theta: Any = 0.0
    # an RMSNorm with a learned scale over the whole q and k projections,
    # before the split into heads (OLMoE: ``q_norm(q_proj(x))``)
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # > 0: the heads' own size, where it is not the embedding over the heads
    # (nemotron_h: 32 heads of 128 beside an embedding of 2,688)
    head_dim: int = 0
    # an RMSNorm over each head's width, one scale shared by the heads
    # (Qwen3: ``q_norm(q_proj(x).view(..., head_dim))``), in ``qk_norm``'s place
    qk_norm_per_head: bool = False
    # rotary positions of several components (Qwen2-VL's ``mrope_section``):
    # how many of a head's frequencies take their angle from each
    mrope_section: tuple = ()
    # index_topk > 0: learned sparse attention (DeepSeek-V3.2-Exp's sparse
    # attention; docs/designs/sparse_attention.md): an indexer of index_heads
    # heads of index_head_dim over ONE key head scores every visible key
    # from the layer's input DETACHED, a query attends to its index_topk
    # best keys alone, and the indexer's KL to the main attention's
    # distribution over them joins the ``losses`` collection
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_kl_weight: float = 1.0
    # > 0: a query reads its last ``window`` keys alone, itself among them
    # (``0 <= t - s < window``; docs/designs/window_attention.md)
    window: int = 0
    # the heads' merged output times ``sigmoid(gate(x))``, a projection of
    # its width, before the output projection (AFMoE's gated attention)
    output_gate: bool = False
    # > 0: the rotary positions turn a head's FIRST ``rotary_dim`` lanes, the
    # others pass through (``partial_rotary_factor`` x the head's width)
    rotary_dim: int = 0

    @nn.compact
    def __call__(self, x, decode_pos=None, positions=None):
        """x: (batch, seq, embed) -> (batch, seq, embed).

        ``decode_pos``: the model's single decode cursor (traced scalar),
        required in decode mode — there is ONE position source of truth,
        not one per layer.  ``positions``: (batch, components, seq) where the
        records carry positions of several components (``mrope_section``);
        None: every component is the token's index."""
        embed = x.shape[-1]
        if not self.head_dim and embed % self.num_heads:
            raise ValueError(
                f"embed dim {embed} not divisible by {self.num_heads} heads"
            )
        head_dim = self.head_dim or embed // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads

        # the projections hand the kernels what they read: rows of merged
        # heads where the kernels take heads out of lanes, else
        # nn.DenseGeneral's 4-D product, whose result XLA writes folded
        q_shape, kv_shape = (
            jax.ShapeDtypeStruct(x.shape[:2] + (heads, head_dim), x.dtype)
            for heads in (self.num_heads, kv_heads)
        )
        layout = attention_ops.flash_layout(q_shape, kv_shape, kv_shape)
        dense = HeadsDense if layout == "lanes" else nn.DenseGeneral

        def _proj(name, heads, norm=None):
            y = dense(
                features=(heads, head_dim), dtype=self.dtype, name=name,
                use_bias=self.use_bias,
            )(x)
            if norm is not None and self.qk_norm_per_head:
                with jax.named_scope("qk_norm"):
                    y = nn.RMSNorm(
                        epsilon=self.norm_eps, dtype=self.dtype, name=norm
                    )(y)
            elif norm is not None:
                with jax.named_scope("qk_norm"):
                    full = y.reshape(*y.shape[:-2], heads * head_dim)
                    y = nn.RMSNorm(
                        epsilon=self.norm_eps, dtype=self.dtype, name=norm
                    )(full).reshape(y.shape)
            return y

        normed = self.qk_norm or self.qk_norm_per_head
        q = _proj("query", self.num_heads, "q_norm" if normed else None)
        k = _proj("key", kv_heads, "k_norm" if normed else None)
        v = _proj("value", kv_heads)
        if self.rope_theta:
            with jax.named_scope("rope"):
                if positions is None or not self.mrope_section:
                    positions = (
                        jnp.arange(x.shape[1])
                        if decode_pos is None
                        else decode_pos + jnp.arange(x.shape[1])
                    )
                sections = tuple(self.mrope_section)
                lead = self.rotary_dim
                q = rope(q, positions, self.rope_theta, sections=sections, lead=lead)
                k = rope(k, positions, self.rope_theta, sections=sections, lead=lead)
        if self.index_topk and (self.decode or not self.causal):
            raise NotImplementedError(
                "sparse attention is built for causal training: the "
                "indexer's key cache and sparse decode are not"
            )
        if self.window and (self.index_topk or not self.causal):
            raise ValueError(
                "a window is built for causal attention without an indexer"
            )
        if self.decode:
            if decode_pos is None:
                raise ValueError("decode mode needs decode_pos")
            out = self._decode_attend(q, k, v, decode_pos)
        elif self.index_topk:
            out = self._sparse_attend(x, q, k, v, positions)
        else:
            out = attention_ops.attention(
                q, k, v, causal=self.causal, window=self.window or None
            )
            if self.window:
                self._sow_block_plan(q, k, v)
        with jax.named_scope("fold"):
            out = out.astype(x.dtype)
        if self.output_gate:
            gate = dense(
                features=(self.num_heads, head_dim), dtype=self.dtype,
                name="gate", use_bias=self.use_bias,
            )(x)
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(gate)
        return dense(
            features=embed, axis=(-2, -1), dtype=self.dtype, name="out",
            use_bias=self.use_bias,
        )(out)

    def _sparse_attend(self, x, q, k, v, positions):
        """The indexer, the selection, attention over the selected set and
        the indexer's loss.  Two detachments keep the gradient paths apart:
        the indexer reads ``stop_gradient(x)`` and its loss's target is the
        main attention's probabilities detached, so the main model's
        parameters see the language-model loss alone and the indexer's
        their KL alone; the selection passes no gradient."""
        from elasticdl_tpu.layers import recompute
        from elasticdl_tpu.ops import sparse_attention as sparse_ops
        from elasticdl_tpu.telemetry.router_load import SELECTION_STATS

        interpret = _kernels_interpret()
        detached = jax.lax.stop_gradient(x)
        # a first pass that is being differentiated (its offers reach the
        # recomputed pass) runs the indexer's backward pass itself, below
        offering = recompute.offers_kept()
        with jax.named_scope("indexer"):
            if offering:
                (qi, ki, weights), indexer_vjp = nn.vjp(
                    lambda layer, x: layer._indexer(x, positions), self, detached
                )
            else:
                qi, ki, weights = self._indexer(detached, positions)
        with jax.named_scope("index_select"):
            # a recomputed pass is handed the threshold its first pass found
            # and checks it by count instead of searching again
            threshold = recompute.found()
            recomputed = threshold is not None
            if not recomputed:
                mask, lse_i, kept, ties, searched, threshold = (
                    sparse_ops.index_select_threshold(
                        qi, ki, weights, self.index_topk, interpret=interpret
                    )
                )
                recompute.offer(threshold)
            else:
                # (its fifth result is where the hint held; a recomputed
                # pass's counters leave the layer nowhere)
                mask, lse_i, kept, ties, searched = (
                    sparse_ops.index_select_hinted(
                        qi, ki, weights, threshold, self.index_topk,
                        interpret=interpret,
                    )
                )
            mask_t = sparse_ops.transpose_mask(mask)
            stats = {
                "kept_keys": jnp.mean(kept),
                "ties_broken": jnp.sum(ties),
                "tie_search_blocks": jnp.mean(searched),
            }
        # (only an apply that asks for ``intermediates`` keeps the mask and
        # the input it was made from: the chip comparison holds the mask to
        # the reference's set, pair by pair, on that same input)
        self.sow("intermediates", "selection", mask)
        self.sow("intermediates", "indexer_input", detached)
        out, lse = attention_ops.selected_flash_attention(
            q, k, v, mask, mask_t, interpret=interpret
        )
        with jax.named_scope("indexer_kl"):
            operands = (
                jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                jax.lax.stop_gradient(lse), mask, qi, ki, weights, lse_i,
            )
            if recomputed:
                # the first pass ran the loss's one call a layer and step
                # and offered its value and the gradient to the indexer's
                # parameters: no kernel here, and nothing to differentiate
                # but the handing over
                found = recompute.found()
                kl = sparse_ops.indexer_kl_found(
                    {name: self.variables["params"][name] for name in found[1]},
                    found,
                )
            elif offering:
                # the gradient variant writes the value too, and the
                # gradient depends on nothing downstream of the layer (the
                # target and the selection are constants, the indexer reads
                # a detached input): it is pushed through the indexer's
                # projections here, where the backward pass would, and
                # offered as the parameters' own (9 MB a layer at the
                # published widths where the three operands' is 37)
                kl, grads = sparse_ops.indexer_kl_with_grads(
                    *operands, interpret=interpret
                )
                with jax.named_scope("indexer"):
                    ours = indexer_vjp(grads)[0]["params"]
                recompute.offer(
                    (kl, {name: ours[name] for name in _INDEXER_PARAMS})
                )
            else:  # a pass nobody differentiates pays for no gradient
                kl = sparse_ops.indexer_kl(*operands, interpret=interpret)
            kl = kl * (self.index_kl_weight / (x.shape[0] * x.shape[1]))
        self.sow(
            "losses", "indexer_kl", kl,
            init_fn=lambda: jnp.zeros((), jnp.float32),
            reduce_fn=lambda _prev, new: new,
        )
        # what telemetry/router_load.py::read_selection reads on demand
        for name, value in stats.items():
            self.sow(
                SELECTION_STATS, name, jax.lax.stop_gradient(value),
                init_fn=lambda: jnp.zeros((), jnp.float32),
                reduce_fn=lambda _prev, new: new,
            )
        return out

    def _indexer(self, detached, positions):
        """The indexer's queries ``(batch, seq, heads, width)``, its one key
        head ``(batch, seq, width)`` and its float32 head weights."""
        heads, width = self.index_heads, self.index_head_dim
        query, key, key_norm, head_weights = _INDEXER_PARAMS

        def dense(features, name):
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        qi = dense((heads, width), query)(detached)
        ki = nn.LayerNorm(
            epsilon=self.norm_eps, dtype=self.dtype, name=key_norm
        )(dense(width, key)(detached))
        weights = dense(heads, head_weights)(detached).astype(
            jnp.float32
        ) * (heads * width) ** -0.5
        if self.rope_theta:
            # the sections in proportion, over the indexer's narrower head
            scale = 2 * sum(self.mrope_section) // width or 1
            sections = tuple(n // scale for n in self.mrope_section)
            qi = rope(qi, positions, self.rope_theta, sections=sections)
            ki = rope(
                ki[..., None, :], positions, self.rope_theta,
                sections=sections,
            )[..., 0, :]
        return qi, ki, weights

    def _sow_block_plan(self, q, k, v):
        """What the window kernels' block plan visited, masked and never
        touched this step, over the batch and the heads
        (``ops.attention.flash_block_plan``'s triple at the blocks the
        kernels chose): what ``telemetry/router_load.py::read_block_plan``
        reads on demand, so that a change that stops skipping is seen
        without a trace."""
        from elasticdl_tpu.telemetry.router_load import BLOCK_PLAN

        plan = attention_ops.window_block_plan(q, k, v, self.window)
        for name, count in zip(("visited", "masked", "skipped"), plan):
            self.sow(
                BLOCK_PLAN, name, jnp.asarray(count, jnp.int32),
                init_fn=lambda: jnp.zeros((), jnp.int32),
                reduce_fn=lambda _prev, new: new,
            )

    def _decode_attend(self, q, k, v, pos):
        """One decode step: append this step's K/V to the cache at
        ``pos``, attend the single query over the filled prefix
        (positions beyond the cursor are masked, and under a window the
        prefix behind its trailing edge)."""
        if not self.max_decode_len:
            raise ValueError("decode=True needs max_decode_len")
        if q.shape[1] != 1:
            raise ValueError(
                f"decode mode consumes one token per call, got seq "
                f"{q.shape[1]}"
            )
        batch, _, kv_heads, head_dim = k.shape
        cache_shape = (batch, self.max_decode_len, kv_heads, head_dim)
        ck = self.variable(
            "cache", "k", lambda: jnp.zeros(cache_shape, k.dtype)
        )
        cv = self.variable(
            "cache", "v", lambda: jnp.zeros(cache_shape, v.dtype)
        )
        if not self.is_initializing():
            # init() runs this call once to create the variables; it must
            # NOT consume cache slot 0
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, pos, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, pos, 0, 0)
            )

        kf, vf = attention_ops.repeat_kv_heads(q, ck.value, cv.value)
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        scores = (
            jnp.einsum(
                "bqhd,bkhd->bhqk",
                q.astype(jnp.float32),
                kf.astype(jnp.float32),
            )
            * scale
        )
        slots = jnp.arange(self.max_decode_len)
        valid = slots <= pos  # filled prefix incl. this step
        if self.window:
            valid = valid & (pos - slots < self.window)
        scores = jnp.where(
            valid[None, None, None, :], scores, attention_ops._NEG_INF
        )
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs, vf.astype(jnp.float32)
        )
        return out.astype(q.dtype)


class LatentSelfAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3, arXiv:2412.19437 section
    2.1.1) for training: queries and keys/values go through low-rank
    latents with an RMSNorm on each, a head's query and key are a
    position-free part beside a rotary part, the rotary key is ONE vector a
    token shared by every head, and the values are narrower than the
    scores (``ops/attention.py`` takes the two widths).  Field names are
    the published configuration's.  The latent cache and the absorbed decode
    form are not built (docs/designs/latent_attention.md)."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    rope_interleave: bool = True  # adjacent pairs | False: rotate halves
    causal: bool = False
    dtype: Any = None
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        heads, nope, rot = (
            self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        )

        def dense(features, name, **kwargs):
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype, name=name, **kwargs
            )

        def norm(name):
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)

        q = dense((heads, nope + rot), "q_b")(
            norm("q_a_norm")(dense(self.q_lora_rank, "q_a")(x))
        )
        latent = dense(self.kv_lora_rank + rot, "kv_a")(x)
        with jax.named_scope("join"):
            kv_latent = latent[..., : self.kv_lora_rank]
        kv = dense((heads, nope + self.v_head_dim), "kv_b")(
            norm("kv_a_norm")(kv_latent)
        )
        # the regions between the modules, by telemetry/op_scopes.py's names
        with jax.named_scope("rope"):
            positions = jnp.arange(x.shape[1])
            # q whole: its rotating part is the tail of each head, turned
            # in place (where the shape keeps the plain form, sliced out,
            # turned and joined back)
            q = rope(
                q, positions, self.rope_theta, self.rope_interleave,
                skip=nope,
            )
            k_rot = rope(
                latent[..., None, self.kv_lora_rank :], positions,
                self.rope_theta, self.rope_interleave,
            )
        with jax.named_scope("join"):
            k = jnp.concatenate(
                [
                    kv[..., :nope],
                    jnp.broadcast_to(k_rot, (*q.shape[:-1], rot)),
                ],
                axis=-1,
            )
            v = kv[..., nope:]
        out = attention_ops.attention(q, k, v, causal=self.causal)
        with jax.named_scope("fold"):
            out = out.astype(x.dtype)
        return dense(x.shape[-1], "out", axis=(-2, -1))(out)


NORMS = ("layernorm", "rmsnorm")
MLPS = ("gelu", "swiglu", "relu2")


def make_norm(kind: str, epsilon: float, dtype, name=None):
    """The block's norm; without a name flax gives it one (``LayerNorm_N``
    / ``RMSNorm_N``)."""
    if kind not in NORMS:
        raise ValueError(f"unknown norm {kind!r}; valid: {NORMS}")
    cls = nn.LayerNorm if kind == "layernorm" else nn.RMSNorm
    return cls(epsilon=epsilon, dtype=dtype, name=name)


# a layer of a ``layer_pattern`` (nemotron_h's ``hybrid_override_pattern``):
# one mixer OR one feed-forward part under one pre-norm residual
# (``w``: the attention part under the block's ``window``, a query reading
# its last ``window`` keys alone; ``*`` beside it reads every earlier key;
# ``c``: the gated short convolution; ``d``: the gated delta rule)
LAYER_KINDS = {
    "*": "attention", "M": "mamba", "E": "experts", "-": "mlp",
    "w": "attention", "c": "conv", "d": "delta",
}


class TransformerBlock(nn.Module):
    """The one pre-norm block.  Every field defaults to what GPT-2-small
    runs (LayerNorm, biases, a 4x GELU MLP, positions added by the model);
    a published architecture is a choice of fields, not another block.
    With ``kind`` (a key of ``LAYER_KINDS``) the block is that one part
    alone, ``x + part(norm(x))``: a hybrid stack's layer.

    The block declares what it decides itself.  A part's own fields come as
    one group a part, pairs of (the part's field name, value), which the
    block hands to the part whole: a new field of a part is declared by the
    part and named by the model (``models/long_seq_transformer.py::
    PART_FIELDS``), and the block does not change."""

    kind: str = ""  # "": attention then feed-forward; else one of LAYER_KINDS
    causal: bool = False
    dropout_rate: float = 0.0
    # autoregressive decoding with a KV cache: the attention part's, and
    # refused by the parts that have no cache built
    decode: bool = False
    max_decode_len: int = 0
    dtype: Any = None  # compute dtype; params stay f32
    norm: str = "layernorm"  # | "rmsnorm"
    norm_eps: float = 1e-6
    # a second norm, on each part's output: x + norm(part(norm(x)))
    norm_outputs: bool = False
    use_bias: bool = True
    # | "swiglu": down(silu(gate(x)) * up(x)) | "relu2": down(relu(up(x))^2)
    mlp: str = "gelu"
    mlp_width: int = 0  # 0: mlp_ratio x the embedding
    mlp_ratio: int = 4
    # MultiHeadSelfAttention's fields (``window``: the ``w`` layers' alone)
    attention_fields: Any = ()
    # LatentSelfAttention's; given, the attention part is the latent mixer
    latent_fields: Any = ()
    # layers.moe.MoEMLP's; given, routed experts replace the dense MLP
    # (shard them over ep via moe_sharding_rules)
    moe_fields: Any = ()
    # layers.mamba.Mamba2Mixer's
    mamba_fields: Any = ()
    # layers.gated_delta.GatedDeltaNet's
    delta_fields: Any = ()

    @nn.compact
    def __call__(
        self, x, training: bool = False, decode_pos=None, positions=None
    ):
        if self.mlp not in MLPS:
            raise ValueError(f"unknown mlp {self.mlp!r}; valid: {MLPS}")
        if self.kind and self.kind not in LAYER_KINDS:
            raise ValueError(
                f"unknown layer kind {self.kind!r}; valid: {list(LAYER_KINDS)}"
            )
        parts = [LAYER_KINDS[self.kind]] if self.kind else [
            "attention", "experts" if self.moe_fields else "mlp"
        ]
        for part in parts:
            run = getattr(self, "_" + part)
            if part == "attention":
                run = functools.partial(run, positions=positions)
            x = self._residual(x, run, training, decode_pos)
        return x

    def _residual(self, x, part, training, decode_pos):
        y = part(
            make_norm(self.norm, self.norm_eps, self.dtype)(x),
            training, decode_pos,
        )
        if self.norm_outputs:
            with jax.named_scope("norm_out"):
                y = make_norm(self.norm, self.norm_eps, self.dtype)(y)
        if self.dropout_rate:
            y = nn.Dropout(self.dropout_rate, deterministic=not training)(y)
        return x + y

    def _attention(self, y, training, decode_pos, positions=None):
        shared = dict(
            causal=self.causal, dtype=self.dtype, norm_eps=self.norm_eps,
            name="attn",
        )
        if self.latent_fields:
            if self.decode:
                raise NotImplementedError(
                    "decoding through a latent-attention layer's cache is "
                    "not built"
                )
            return LatentSelfAttention(**shared, **dict(self.latent_fields))(y)
        fields = dict(self.attention_fields)
        if self.kind == "w":
            if fields.get("window", 0) < 1:
                raise ValueError("a layer of kind 'w' needs a window")
        else:
            fields["window"] = 0
        return MultiHeadSelfAttention(
            decode=self.decode, max_decode_len=self.max_decode_len,
            use_bias=self.use_bias, **shared, **fields,
        )(y, decode_pos=decode_pos, positions=positions)

    def _mamba(self, y, training, decode_pos):
        from elasticdl_tpu.layers.mamba import Mamba2Mixer

        if self.decode:
            raise NotImplementedError(
                "decoding through a Mamba-2 layer's state is not built"
            )
        return Mamba2Mixer(
            norm_eps=self.norm_eps, dtype=self.dtype, name="mamba",
            **dict(self.mamba_fields),
        )(y)

    def _delta(self, y, training, decode_pos):
        from elasticdl_tpu.layers.gated_delta import GatedDeltaNet

        if self.decode:
            raise NotImplementedError(
                "decoding through a gated-delta-rule layer's state is not "
                "built"
            )
        return GatedDeltaNet(
            norm_eps=self.norm_eps, dtype=self.dtype, name="gdn",
            **dict(self.delta_fields),
        )(y)

    def _conv(self, y, training, decode_pos):
        from elasticdl_tpu.layers.short_conv import ShortConv

        if self.decode:
            raise NotImplementedError(
                "decoding through a short-convolution layer's cache is not "
                "built"
            )
        return ShortConv(dtype=self.dtype, name="conv")(y)

    def _experts(self, y, training, decode_pos):
        from elasticdl_tpu.layers.moe import MoEMLP

        return MoEMLP(dtype=self.dtype, name="moe", **dict(self.moe_fields))(
            y, training=training
        )

    def _mlp(self, y, training, decode_pos):
        width = self.mlp_width or y.shape[-1] * self.mlp_ratio

        # named for the shared megatron tp rules (default_tp_rules)
        def dense(features, name):
            return nn.Dense(
                features, dtype=self.dtype, use_bias=self.use_bias, name=name
            )

        with jax.named_scope("mlp"):
            if self.mlp == "swiglu":
                hidden = nn.silu(dense(width, "mlp_gate")(y)) * dense(
                    width, "mlp_up"
                )(y)
            elif self.mlp == "relu2":
                hidden = jnp.square(nn.relu(dense(width, "mlp_up")(y)))
            else:
                hidden = nn.gelu(dense(width, "mlp_up")(y))
            return dense(y.shape[-1], "mlp_down")(hidden)


def _rope_takes_kernel(x, skip: int = 0) -> bool:
    """Whether :func:`rope` hands ``x`` to ``ops/rotary.py``'s kernel: the
    shape tiles (``rotary.rotate_tile``, which says which shapes do) and no
    ``sp`` axis shards the sequence, where GSPMD partitions the plain form
    and the kernel, mapped over the batch alone, would gather it."""
    if not rotary_ops.rotate_tile(x.shape, skip):
        return False
    mesh, sp_axis, _ = on_mesh.get_attention_mesh()
    return not (
        mesh is not None
        and sp_axis in mesh.axis_names
        and mesh.shape[sp_axis] > 1
    )


def rope(
    x, positions, rule, interleave: bool = False, sections=(), skip: int = 0,
    lead: int = 0,
):
    """Rotary positions (Su et al. 2021) over ``x``'s last axis behind its
    first ``skip`` lanes, which pass through (a head whose rotating part is
    its tail; 0: the whole of it): ``x`` (batch, seq, heads, d),
    ``positions`` (seq,).  ``rule`` a float, the base ``theta``: pair ``i``
    of the ``d - skip`` rotating lanes turns by ``positions *
    theta^(-2i/(d - skip))``; a ``ops/rotary.py::Yarn``: by its blended
    frequencies, cos and sin scaled (docs/designs/yarn_rope.md).  The pairs
    are ``(x_i, x_{i+d/2})``, the rotate-half convention of HF
    ``apply_rotary_pos_emb``, or with ``interleave`` the adjacent ``(x_2i,
    x_2i+1)`` of the original and of ``rope_interleave``.  Computed in
    float32.

    ``lead`` > 0: the rotating part is the head's FIRST ``lead`` lanes (pair
    ``i`` by ``theta^(-2i/lead)``) and the lanes behind them pass through
    (``partial_rotary_factor``); the plain form alone, which
    ``ops/rotary.py``'s kernel does not take yet
    (docs/designs/rotary_kernel.md).

    ``positions`` (batch, components, seq) with ``sections`` (Qwen2-VL's
    multimodal RoPE): frequency ``i`` takes its angle from the component
    whose section it lies in, ``sections[c]`` frequencies each in order
    (temporal, height, width).  Where every component is the token's index
    (text) that is the rule above, and (seq,) positions take it.

    Which code runs is chosen from the shapes (:func:`_rope_takes_kernel`):
    rotating lanes that are whole 128-lane tiles, behind whole tiles that
    pass through, or the 64 lanes of an array's one head, with at least one
    tile of 512 rows and the sequence whole on its device, is
    ``ops/rotary.py``'s single-pass kernel (the same products and sums a
    number, in float32; forward, recomputed and backward), which reads and
    writes the whole head in place; everything else (a decode step, a small
    model, a sequence sharded over ``sp``, several 64-wide heads, any other
    width) is :func:`rope_plain` on the rotating lanes, joined to the
    others."""
    if lead:
        if skip:
            raise ValueError("a rotating head and a rotating tail at once")
        turned = rope_plain(
            x[..., :lead], positions, rule, interleave, sections
        )
        return jnp.concatenate([turned, x[..., lead:]], axis=-1)
    if not _rope_takes_kernel(x, skip):
        if not skip:
            return rope_plain(x, positions, rule, interleave, sections)
        turned = rope_plain(
            x[..., skip:], positions, rule, interleave, sections
        )
        return jnp.concatenate([x[..., :skip], turned], axis=-1)
    # one pass of ops/rotary.py's kernel over the folded form the attention
    # kernels take: the two transposes are layouts for XLA to assign
    by_batch = positions.ndim == 3
    out = on_mesh.over_batch(
        lambda x, positions, interpret: rotary_ops.rotate(
            x, positions, rule, tuple(sections), interleave, skip, interpret
        ),
        (x.transpose(0, 2, 1, 3),) + ((positions,) if by_batch else ()),
        () if by_batch else (positions,),
    )
    return out.transpose(0, 2, 1, 3)


def rope_plain(x, positions, rule, interleave: bool = False, sections=()):
    """:func:`rope` in plain ``jnp``, for every shape."""
    half = x.shape[-1] // 2
    angles = rotary_ops.angles(positions, rule, half, sections)
    if positions.ndim == 3:
        angles = angles[:, :, None, :]
        cos, sin = rotary_ops.scaled(rule, jnp.cos(angles), jnp.sin(angles))
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
    cos, sin = rotary_ops.scaled(
        rule, jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    )
    if interleave:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if interleave:
        return jnp.stack(turned, axis=-1).reshape(x.shape).astype(x.dtype)
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def sinusoidal_positions(seq_len: int, dim: int) -> jnp.ndarray:
    """Fixed sinusoidal position encoding (seq, dim) — parameter-free, so
    a sequence-sharded activation needs no position-table gather."""
    pos = jnp.arange(seq_len)[:, None].astype(jnp.float32)
    div = jnp.exp(
        jnp.arange(0, dim, 2).astype(jnp.float32)
        * (-jnp.log(10000.0) / dim)
    )
    enc = jnp.zeros((seq_len, dim), jnp.float32)
    enc = enc.at[:, 0::2].set(jnp.sin(pos * div))
    enc = enc.at[:, 1::2].set(jnp.cos(pos * div))
    return enc
