"""Long-context causal transformer LM — the sequence-parallel flagship.

No reference counterpart (the reference zoo is CNN/DNN/FM recommenders,
SURVEY §2.10); this model exists because long-context training is a
first-class capability of the TPU build: its attention dispatches to the
pallas flash kernel on one device and to ring attention over the ``sp``
mesh axis when the sequence is sharded (``--mesh_shape dp=2,sp=4``).

Spec contract is the standard model-zoo surface (custom_model /
dataset_fn / loss / optimizer / eval_metrics_fn), so the same CLI trains
it: records are token sequences (``synthetic.gen_sequence``), the task
is next-token prediction.
"""

from __future__ import annotations

import functools
import operator
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.data.reader import decode_example
from elasticdl_tpu.layers.attention import (
    LAYER_KINDS,
    TransformerBlock,
    make_norm,
    sinusoidal_positions,
)
from elasticdl_tpu.layers.recompute import remat_with_findings
from elasticdl_tpu.ops import rotary
from elasticdl_tpu.telemetry.router_load import (
    LOSS_OBSERVED,
    LOSS_PARTS,
    observed_names,
)
from elasticdl_tpu.trainer.losses import (
    softmax_cross_entropy_with_integer_labels,
)
from elasticdl_tpu.trainer.metrics import Accuracy
from elasticdl_tpu.trainer.state import Modes

VOCAB = 256
# a looped model's loss by its parts (``looped_parts``)
LOOPED_PARTS = ("expected_ce", "exit_entropy")


# The parts' fields by the model's names, a group of
# ``layers/attention.py::TransformerBlock`` each: the part -> {the model's
# field: the part's own name for it}.  ``attention`` is
# MultiHeadSelfAttention, ``latent`` LatentSelfAttention (the two kinds of
# attention part share the heads and the rotary base), ``moe``
# layers/moe.py::MoEMLP, ``mamba`` layers/mamba.py::Mamba2Mixer, ``delta``
# layers/gated_delta.py::GatedDeltaNet
# (layers/short_conv.py::ShortConv, the ``c`` layers' part, has no field the
# model sets: its three taps are its own default).  A new field
# of a part is declared there, as a field of the model below, and on one line
# here; every field of the model that is not here is the model's own or the
# block's.
PART_FIELDS = {
    "attention": {
        "num_heads": "num_heads",
        "rope_theta": "rope_theta",
        "num_kv_heads": "num_kv_heads",
        "head_dim": "head_dim",
        "qk_norm": "qk_norm",
        "qk_norm_per_head": "qk_norm_per_head",
        "mrope_section": "mrope_section",
        "index_topk": "index_topk",
        "index_heads": "index_heads",
        "index_head_dim": "index_head_dim",
        "index_kl_weight": "index_kl_weight",
        "sliding_window": "window",
        "output_gate": "output_gate",
        "partial_rotary_factor": "rotary_dim",
    },
    "latent": {
        "num_heads": "num_heads",
        "rope_theta": "rope_theta",
        "q_lora_rank": "q_lora_rank",
        "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim",
        "rope_interleave": "rope_interleave",
    },
    "moe": {
        "num_experts": "num_experts",
        "experts_per_token": "experts_per_token",
        "expert_width": "expert_width",
        "norm_topk_prob": "norm_topk_prob",
        "router_aux_weight": "aux_loss_weight",
        "router_z_weight": "z_loss_weight",
        "router_scoring": "scoring",
        "selection_bias": "selection_bias",
        "selection_bias_rate": "selection_bias_rate",
        "routed_scaling": "routed_scaling",
        "expert_kind": "expert_kind",
        "shared_expert_width": "shared_width",
        "experts_held": "experts_held",
        "first_expert": "first_expert",
        "router_trains": "router_trains",
        "shared_expert_gate": "shared_gated",
    },
    "mamba": {
        "mamba_heads": "num_heads",
        "mamba_head_dim": "head_dim",
        "ssm_groups": "groups",
        "ssm_state": "state_size",
        "conv_kernel": "conv_kernel",
        "ssd_chunk": "chunk",
    },
    "delta": {
        "linear_key_heads": "num_key_heads",
        "linear_value_heads": "num_value_heads",
        "linear_key_dim": "key_dim",
        "linear_value_dim": "value_dim",
        "conv_kernel": "conv_kernel",
        "delta_chunk": "chunk",
    },
}

# the kinds of attention layer a published ``rope_parameters`` names a rule
# for: every earlier key (``*`` and the plain block), or a window (``w``)
ROPE_KINDS = ("full_attention", "sliding_attention")


class TransformerLM(nn.Module):
    vocab_size: int = VOCAB
    embed_dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    dropout_rate: float = 0.0
    num_kv_heads: int = 0  # > 0: grouped-query attention
    decode: bool = False  # one-token-per-call decoding with KV caches
    max_decode_len: int = 0
    # compute dtype (e.g. "bfloat16"): activations and matmuls run in it,
    # parameters stay f32; the loss casts logits back up
    dtype: Any = None
    # the block's fields (layers/attention.py::TransformerBlock); the
    # defaults are GPT-2-small's, a published architecture names its own
    # (perf/configs/olmoe_1b7b.json: rmsnorm, no bias, rope, qk_norm, experts)
    norm: str = "layernorm"  # | "rmsnorm"
    norm_eps: float = 1e-6
    use_bias: bool = True  # the head's too
    # added at the embedding | "rope" | "none": no position signal at all
    positions: str = "sinusoidal"
    rope_theta: float = 10000.0
    qk_norm: bool = False
    mlp: str = "gelu"  # | "swiglu"
    mlp_width: int = 0  # 0: four times the embedding
    num_experts: int = 0  # > 0: routed SwiGLU experts, sharded over ep
    experts_per_token: int = 2
    expert_width: int = 0  # 0: the dense MLP's width
    norm_topk_prob: bool = False
    # weights of the load-balance loss and the router z-loss, over the
    # mean of the layers' losses
    router_aux_weight: float = 0.01
    router_z_weight: float = 0.001
    # a hybrid stack (perf/configs/nemotron_twotower_30b_a3b.json): one
    # letter a layer, a key of layers.attention.LAYER_KINDS, each layer one
    # mixer or one feed-forward part; "" is the block above for every layer
    layer_pattern: str = ""
    head_dim: int = 0  # 0: the embedding over the heads
    remat_layers: bool = False  # recompute each layer in the backward pass
    # the expert layers' further fields (layers/moe.py::MoEMLP)
    router_scoring: str = "softmax"  # | "sigmoid"
    selection_bias: bool = False
    selection_bias_rate: float = 0.001
    routed_scaling: float = 1.0
    expert_kind: str = "swiglu"  # | "relu2"
    shared_expert_width: int = 0
    experts_held: int = 0  # 0: all; else this deployment's share
    first_expert: int = 0
    # False: a cut's routers take no step (their gradient lacks the absent
    # experts' part and would send every pair to the held ones)
    router_trains: bool = True
    # the Mamba-2 layers' (layers/mamba.py::Mamba2Mixer)
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    ssd_chunk: int = 128
    # the gated-delta-rule layers' (layers/gated_delta.py::GatedDeltaNet; the
    # ``d`` layers of ``layer_pattern``): key heads and value heads of their
    # own widths, a value head reading key head ``h // (values / keys)``; the
    # convolution's taps are ``conv_kernel``
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    delta_chunk: int = 128
    # kv_lora_rank > 0: the attention parts are latent attention
    # (layers/attention.py::LatentSelfAttention), fields by their names there
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = True
    # multi-token prediction (arXiv:2412.19437 section 2.2): this many
    # further modules, each one more block behind a projection of
    # [norm(h) ; norm(embedding of the next token)], sharing tok_embed and
    # lm_head; in training the model then returns their logits beside the
    # main ones and ``loss`` adds mtp_weight times their mean loss
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # the attention parts' further fields (layers/attention.py::
    # MultiHeadSelfAttention): an RMSNorm a head on q and k; rotary
    # positions of several components, read from the records' ``positions``
    # (batch, components, seq) where they carry them; index_topk > 0: learned
    # sparse attention (docs/designs/sparse_attention.md), the indexers' KL
    # loss at index_kl_weight a layer in the ``losses`` collection
    qk_norm_per_head: bool = False
    mrope_section: Any = ()
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_kl_weight: float = 1.0
    # window and full attention mixed (docs/designs/window_attention.md;
    # perf/configs/trinity_mini_26b_a3b.json): a ``w`` of ``layer_pattern``
    # is an attention part whose queries read their last ``sliding_window``
    # keys alone, a ``*`` reads every earlier key; with
    # ``full_attention_rope`` False the rotary positions turn the ``w``
    # parts alone and the ``*`` parts get no position signal
    sliding_window: int = 0
    full_attention_rope: bool = True
    # the rotary rule by the attention layer's kind (``ROPE_KINDS``), as a
    # published ``rope_parameters`` gives it: {"sliding_attention":
    # {"rope_type": "default", "rope_theta": ...}, "full_attention":
    # {"rope_type": "yarn", "rope_theta", "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "attention_factor"}} (``ops/rotary.py::rule_of``;
    # docs/designs/yarn_rope.md); a kind it does not name, and every layer
    # where it is None, turns by ``rope_theta`` (``_rope_rules``)
    rope_parameters: Any = None
    # the attention parts' output times sigmoid(gate(x)) before the output
    # projection; a second norm on every part's output (x + norm(part(
    # norm(x)))); the embedding times sqrt(embed_dim) (muP)
    output_gate: bool = False
    # < 1: the rotary positions turn that share of a head's width, its first
    # lanes (rotate-half within them), and the rest pass through
    partial_rotary_factor: float = 1.0
    # the shared expert's output times sigmoid(x w_g) (layers/moe.py)
    shared_expert_gate: bool = False
    norm_outputs: bool = False
    scale_embedding: bool = False
    # the head is the token embedding: logits = norm(x) @ tok_embed^T, one
    # parameter whose gradient is the sum of its two uses (no ``lm_head``)
    tie_embedding: bool = False
    # > 1: a looped model (docs/designs/looped_layers.md;
    # perf/configs/ouro_2p6b.json): the stack runs this many times on ONE set
    # of block parameters, each pass reading the final norm of the pass
    # before; the norm, an exit gate (one Dense(1) for all passes) and the
    # head follow every pass.  A training forward returns every pass's exit
    # by what makes it (``looped_outputs``) and ``loss`` is the exit
    # distribution's expected cross-entropy less ``exit_entropy_weight``
    # times the distribution's entropy; any other forward returns the last
    # pass's logits
    loop_steps: int = 1
    exit_entropy_weight: float = 0.1

    def _rotary_dim(self) -> int:
        """The lanes of a head the rotary positions turn, 0 for all."""
        if self.partial_rotary_factor == 1.0:
            return 0
        width = self.head_dim or self.embed_dim // self.num_heads
        turned = int(width * self.partial_rotary_factor)
        if not 0 < turned < width or turned % 2:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {width}"
            )
        return turned

    def _rope_rules(self) -> dict:
        """What each kind of attention layer turns its positions by: a
        base, an ``ops/rotary.py::Yarn``, or 0.0 for no rotation."""
        full, _ = ROPE_KINDS
        stated = {
            kind: rotary.rule_of(group)
            for kind, group in (self.rope_parameters or {}).items()
        }
        if set(stated) - set(ROPE_KINDS):
            raise ValueError(
                f"rope_parameters names {sorted(stated)}; valid: {ROPE_KINDS}"
            )
        if stated and self.positions != "rope":
            raise ValueError(
                f"rope_parameters with positions {self.positions!r}: its "
                "rules turn rotary positions alone"
            )
        if full in stated and not self.full_attention_rope:
            raise ValueError(
                "rope_parameters gives the full layers a rule and "
                "full_attention_rope False gives them none"
            )
        if self.positions != "rope":
            return dict.fromkeys(ROPE_KINDS, 0.0)
        rules = {kind: stated.get(kind, self.rope_theta) for kind in ROPE_KINDS}
        return rules if self.full_attention_rope else {**rules, full: 0.0}

    @nn.compact
    def __call__(self, features, training: bool = False):
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        tokens = jnp.asarray(tokens).astype(jnp.int32)
        components = (
            features.get("positions")
            if self.mrope_section and isinstance(features, dict)
            else None
        )
        if self.positions not in ("sinusoidal", "rope", "none"):
            raise ValueError(f"unknown positions {self.positions!r}")
        pattern = self.layer_pattern
        if pattern and (
            len(pattern) != self.num_layers or set(pattern) - set(LAYER_KINDS)
        ):
            raise ValueError(
                f"layer_pattern {pattern!r} is not {self.num_layers} of "
                f"{list(LAYER_KINDS)}"
            )
        expert_layers = pattern.count("E") if pattern else self.num_layers
        block_class = TransformerBlock
        if self.remat_layers:
            # (self, x, training, decode_pos): training is a Python bool
            # a sparse layer's recomputed pass checks the selection its
            # first pass found (layers/recompute.py) instead of searching
            remat = remat_with_findings if self.index_topk else nn.remat
            block_class = remat(TransformerBlock, static_argnums=(2,))
        sinusoidal = self.positions == "sinusoidal"
        tok_embed = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype,
            name="tok_embed",
        )
        x = tok_embed(tokens)
        if self.scale_embedding:
            x = x * jnp.asarray(self.embed_dim**0.5, x.dtype)
        # parameter-free positions: a sequence-sharded activation adds its
        # slice of the encoding without any table gather
        decode_pos = None
        if self.decode:
            # the ONE decode cursor: position encoding and every layer's
            # KV-cache write derive from it
            pos_var = self.variable(
                "cache", "pos", lambda: jnp.zeros((), jnp.int32)
            )
            decode_pos = pos_var.value
            if sinusoidal:
                enc = sinusoidal_positions(
                    self.max_decode_len, self.embed_dim
                )
                x = x + jax.lax.dynamic_slice_in_dim(
                    enc, decode_pos, 1
                )[None, :, :].astype(x.dtype)
            if not self.is_initializing():  # init must not advance
                pos_var.value = decode_pos + 1
        elif sinusoidal:
            x = x + sinusoidal_positions(tokens.shape[1], self.embed_dim)[
                None, :, :
            ].astype(x.dtype)

        # what the parts are given: the model's fields under the parts'
        # names, but for the six values the model decides itself
        decided = dict(
            rope_theta=self.rope_theta if self.positions == "rope" else 0.0,
            mrope_section=tuple(self.mrope_section),
            # 0: the dense MLP's width (and MoEMLP's own 0 is its 4x)
            expert_width=self.expert_width or self.mlp_width,
            # over the mean of the layers' losses
            router_aux_weight=self.router_aux_weight / max(1, expert_layers),
            router_z_weight=self.router_z_weight / max(1, expert_layers),
            # the share of a head's width as its lanes
            partial_rotary_factor=self._rotary_dim(),
        )
        # a group that is given chooses its part (TransformerBlock)
        absent = {
            "latent": not self.kv_lora_rank, "moe": not self.num_experts,
            "delta": not self.linear_value_heads,
        }
        groups = {
            part + "_fields": tuple(
                (field, decided.get(name, getattr(self, name)))
                for name, field in fields.items()
                if not absent.get(part)
            )
            for part, fields in PART_FIELDS.items()
        }
        rope_rules = self._rope_rules()

        def block(kind, name):
            # an attention part turns its positions by its kind's rule
            rule = rope_rules[ROPE_KINDS[kind == "w"]]
            attention = tuple(
                (field, rule if field == "rope_theta" else value)
                for field, value in groups["attention_fields"]
            )
            return block_class(
                kind=kind,
                causal=True,
                dropout_rate=self.dropout_rate,
                decode=self.decode,
                max_decode_len=self.max_decode_len,
                dtype=self.dtype,
                norm=self.norm,
                norm_eps=self.norm_eps,
                norm_outputs=self.norm_outputs,
                use_bias=self.use_bias,
                mlp=self.mlp,
                mlp_width=self.mlp_width,
                **{**groups, "attention_fields": attention},
                name=name,
            )

        def norm(name=None):
            return make_norm(self.norm, self.norm_eps, self.dtype, name)

        def stack(x):
            for layer in range(self.num_layers):
                x = block(pattern[layer] if pattern else "", f"block_{layer}")(
                    x, training, decode_pos, components
                )
            return x

        looped = self.loop_steps > 1
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps} is not 1 or more")
        if looped and self.decode:
            raise NotImplementedError(
                "decoding through a loop of layers (a KV cache a layer and "
                "pass) is not built"
            )
        kept_apart = [
            name for name in ("mtp_depth", "index_topk", "num_experts", "sliding_window")
            if getattr(self, name)
        ]
        if looped and kept_apart:
            # (each keeps a collection a layer, which a loop would have to
            # keep a layer and pass)
            raise ValueError(f"{kept_apart} in a loop of layers is not built")
        if not looped:
            x = stack(x)
        if self.index_topk and (training or self.is_initializing()):
            # asks trainer/step.py for the loss by its parts: the sown
            # losses join them under their own names
            for part in ("main", "indexer_kl") + tuple(
                name
                for name, weight in (
                    ("moe_load_balance", self.router_aux_weight),
                    ("moe_router_z", self.router_z_weight),
                )
                if weight and self.num_experts and expert_layers
            ):
                self.variable(
                    LOSS_PARTS, part, lambda: jnp.zeros((), jnp.float32)
                )
        if self.tie_embedding:
            if self.use_bias:
                raise ValueError("a tied head has no bias: use_bias=False")

            def lm_head(h):
                # the head's time under the head's name, the embedding's
                # parameter (telemetry/op_scopes.py)
                with jax.named_scope("lm_head"):
                    return tok_embed.attend(h)
        else:
            lm_head = nn.Dense(
                self.vocab_size, dtype=self.dtype, use_bias=self.use_bias,
                name="lm_head",
            )
        if looped:
            # one pass: the stack, then the exit's norm (whose output the
            # next pass reads) and gate.  The passes are ONE scan body, its
            # modules made inside the scan's scope, once (``mdl`` is this
            # module there), the parameters broadcast to every pass: a
            # shared weight's gradient is summed in the backward scan's
            # carry.  (Unrolled, 32 applications in the program, the same
            # step compiled in 173 s against 97 and ran 2.2% slower:
            # docs/designs/looped_layers.md)
            def one_pass(mdl, h, _):
                h = stack(h)
                with jax.named_scope("exit"):
                    with jax.named_scope("norm"):
                        h = norm()(h)
                    # over sqrt(width), as attention's scores are over
                    # sqrt(head_dim): a product with a normed state moves by
                    # width x rate a step of Adam, which at the logit's own
                    # scale took the gate to "leave after the first pass"
                    # inside twenty steps at 2,048 wide (PERF.md, PR 53)
                    gate = nn.Dense(
                        1, dtype=jnp.float32, name="exit_gate",
                        kernel_init=nn.initializers.zeros,
                    )(h.astype(jnp.float32))[..., 0] * self.embed_dim**-0.5
                return h, (h, gate)

            # (the loop's own ops, the carry's copies and the stacked
            # exits, get the loop's name: telemetry/op_scopes.py::LOOP)
            with jax.named_scope("loop"):
                x, (states, gates) = nn.scan(
                    one_pass,
                    variable_broadcast="params",
                    split_rngs={"params": False, "dropout": True},
                    length=self.loop_steps,
                )(self, x, None)
            if training or self.is_initializing():
                # asks trainer/step.py to leave the loss's two parts, and
                # what the loss saw of every pass, in the state
                for collection, names in (
                    (LOSS_PARTS, LOOPED_PARTS),
                    (LOSS_OBSERVED, observed_names(self.loop_steps)),
                ):
                    for name in names:
                        self.variable(
                            collection, name, lambda: jnp.zeros((), jnp.float32)
                        )
            if self.is_initializing() or not training:
                # (the last pass's logits; ``init`` makes the head's
                # parameters here)
                return lm_head(x)
            # every pass's exit by what makes it: four passes' logits side by
            # side are 3.2 GB at 8,192 x 49,152, so the head is the loss's to
            # apply, a pass at a time (``looped_parts``)
            return {
                "exit_states": states,  # (passes, batch, seq, embed)
                "exit_gates": gates,  # (passes, batch, seq): the gate's logit
                "head": (
                    {"kernel": tok_embed.embedding.T} if self.tie_embedding
                    else self.variables["params"]["lm_head"]
                ),
                "exit_entropy_weight": jnp.float32(self.exit_entropy_weight),
            }
        logits = lm_head(norm()(x))
        if self.decode or not (
            self.mtp_depth and (training or self.is_initializing())
        ):
            return logits
        # module k predicts token i + k + 1 at position i from the state
        # below it and token i + k.  Every module runs at all positions (the
        # kernels' blocks divide the sequence): the last k columns read
        # tokens rolled round from the front, the causal mask keeps them
        # from every earlier column, and ``loss`` leaves them out
        for part in ("main", "mtp"):
            # asks trainer/step.py to leave the two losses in the state
            self.variable(LOSS_PARTS, part, lambda: jnp.zeros((), jnp.float32))
        mtp_logits = []
        for k in range(1, self.mtp_depth + 1):
            ahead = tok_embed(jnp.roll(tokens, -k, axis=1))
            x = nn.Dense(
                self.embed_dim, use_bias=False, dtype=self.dtype,
                name=f"mtp_{k}_proj",
            )(
                jnp.concatenate(
                    [
                        norm(f"mtp_{k}_hnorm")(x),
                        norm(f"mtp_{k}_enorm")(ahead),
                    ],
                    axis=-1,
                )
            )
            x = block("", f"mtp_{k}_block")(x, training, decode_pos)
            mtp_logits.append(lm_head(norm(f"mtp_{k}_norm")(x)))
        return {
            "logits": logits,
            "mtp_logits": tuple(mtp_logits),
            # a value a row: the step's masked loss maps ``loss`` over rows
            "mtp_weight": jnp.full(tokens.shape[:1], self.mtp_weight),
        }


def custom_model(**kwargs):
    return TransformerLM(**kwargs)


def sharding_rules(mesh):
    """Megatron-style tensor parallelism over ``tp``: the shared default
    rule set (QKV sharded by head, attn-out/MLP paired so each block
    needs exactly one psum — GSPMD inserts it); everything unmatched
    falls through to the default fsdp/replicated policy."""
    from elasticdl_tpu.layers.moe import moe_sharding_rules
    from elasticdl_tpu.parallel.sharding import default_tp_rules

    rules = []
    if mesh.shape.get("ep", 1) > 1:
        rules += moe_sharding_rules()
    if mesh.shape.get("tp", 1) > 1:
        rules += default_tp_rules()
    return tuple(rules)


def exit_distribution(gates):
    """``log p_t`` over the passes (the leading axis) from the exit gate's
    logits: ``p_t = g_t prod_{j<t} (1 - g_j)``, the last pass taking what is
    left, ``prod_{j<P} (1 - g_j)`` (its own gate is not read); ``g =
    sigmoid(logit)``.  Sums to 1 a token; (1/2, 1/4, 1/8, 1/8) at 0."""
    log_p, stayed = [], jnp.zeros_like(gates[0])
    for gate in gates[:-1]:
        log_p.append(stayed + jax.nn.log_sigmoid(gate))
        stayed = stayed + jax.nn.log_sigmoid(-gate)
    return jnp.stack(log_p + [stayed])


@jax.custom_vjp
def _exits_cross_entropy(states, head, labels, token_weight):
    return _exits_fwd(states, head, labels, token_weight)[0]


def _exits_fwd(states, head, labels, token_weight):
    """``exits_cross_entropy`` and, as residuals, its gradients: a pass's
    logits, their cross-entropy and its gradient at ``token_weight`` are
    formed in ONE loop body and the gradient is applied there, to the pass's
    state and, summed over the passes in float32, to the head.  No pass's
    logits leave the body, and nothing is left for the backward rule to
    multiply."""
    dtype = states.dtype
    kernel = head["kernel"].astype(dtype)
    bias = head["bias"].astype(dtype) if "bias" in head else None

    def one_pass(d_head, exit_):
        state, weight = exit_
        # a pass's state as an array of its own: without the barrier XLA
        # slices the stacked states inside the products' fusions, with it
        # the slice is prefetched into fast memory as the loop of before
        # had it (the head's gradient 42.1 ms a step for 49.0 at 8,192 x
        # 2,048 x 49,152, the logits 35.3 for 37.0; PERF.md section 6, PR 57)
        state = jax.lax.optimization_barrier(state)
        with jax.named_scope("lm_head"):
            logits = state @ kernel
            if bias is not None:
                logits = logits + bias
        # trainer/losses.py's cross-entropy and what autodiff makes of it at
        # the tokens' weights: float32 statistics, the gradient rounded to
        # the logits' dtype
        cross_entropy, pullback = jax.vjp(
            lambda logits: softmax_cross_entropy_with_integer_labels(
                logits, labels
            ),
            logits,
        )
        (d_logits,) = pullback(weight)
        with jax.named_scope("lm_head"):
            d_state = d_logits @ kernel.T
            d_pass = {
                "kernel": jnp.einsum(
                    "bsd,bsv->dv", state, d_logits,
                    preferred_element_type=jnp.float32,
                )
            }
            if bias is not None:
                d_pass["bias"] = jnp.sum(
                    d_logits, axis=(0, 1), dtype=jnp.float32
                )
        d_head = jax.tree_util.tree_map(jnp.add, d_head, d_pass)
        return d_head, (cross_entropy, d_state)

    zeros = {
        name: jnp.zeros(head[name].shape, jnp.float32) for name in head
    }
    d_head, (cross_entropy, d_states) = jax.lax.scan(
        one_pass, zeros, (states, token_weight)
    )
    total = jnp.sum(token_weight * cross_entropy)
    d_head = {name: d_head[name].astype(head[name].dtype) for name in head}
    return (total, cross_entropy), (d_states, d_head, cross_entropy)


def _exits_bwd(residuals, cotangents):
    d_states, d_head, cross_entropy = residuals
    # (``cross_entropy`` is handed out under ``stop_gradient``)
    scale, _ = cotangents

    def scaled(d):
        # (in float32, as autodiff scales the logits' gradient before it
        # rounds it; the step's cotangent is 1 and XLA folds this away)
        return (scale * d.astype(jnp.float32)).astype(d.dtype)

    return (
        scaled(d_states),
        jax.tree_util.tree_map(scaled, d_head),
        None,
        scale * cross_entropy,
    )


_exits_cross_entropy.defvjp(_exits_fwd, _exits_bwd)


def exits_cross_entropy(states, head, labels, token_weight):
    """``(sum(token_weight * CE), CE)`` of a looped model's exits: ``CE`` the
    ``(passes, batch, seq)`` float32 cross-entropies of ``labels`` under
    ``states[t] @ head``, handed out with no gradient path.  The sum's
    gradient reaches the states and the head through products made beside
    the logits (``_exits_fwd``), which takes a token's weight in the
    loss to be known before its logits are: a weight gradient summed over
    tokens can afterwards be scaled by a scalar, not by token or row.  So
    the sum is a scalar, and ``token_weight`` gets ``CE`` times its
    cotangent, through which the gates get theirs."""
    total, cross_entropy = _exits_cross_entropy(
        states, head, labels, token_weight
    )
    return total, jax.lax.stop_gradient(cross_entropy)


def looped_parts(labels, outputs, weights=None) -> dict | None:
    """A looped model's loss by its parts (arXiv:2510.25741, stage I: gate
    and model trained together), each the mean over rows that
    ``trainer/step.py::weighted_mean_loss`` takes with ``weights`` (a row of
    weight 0 adds nothing to it or to any gradient) and the plain mean
    without: ``expected_ce``, the mean over tokens of ``sum_t p_t CE_t``, and
    ``exit_entropy``, ``-beta`` times the mean of ``H(p) = -sum_t p_t log
    p_t``; under ``LOSS_OBSERVED`` what is no term of the loss, each pass's
    own mean cross-entropy ``ce_t`` and mean exit probability ``exit_t``.
    The head is applied here (``exits_cross_entropy``), so the rows' weights
    are taken here too; None for outputs of any other kind."""
    if not (isinstance(outputs, dict) and "exit_states" in outputs):
        return None
    rows, seq = labels.shape
    if weights is None:
        share = jnp.full((rows,), 1.0 / rows, jnp.float32)
    else:
        # a row's share of ``weighted_mean_loss``'s mean, its guard too
        share = weights.astype(jnp.float32)
        share = share / jnp.maximum(jnp.sum(share), 1.0)
    log_p = exit_distribution(outputs["exit_gates"])
    p = jnp.exp(log_p)
    expected, cross_entropy = exits_cross_entropy(
        outputs["exit_states"], outputs["head"], labels,
        p * (share / seq)[:, None],
    )
    entropy = -jnp.sum(p * log_p, axis=0)

    def mean(per_token):
        return jnp.sum(share * per_token.mean(axis=-1))

    seen = {"ce": cross_entropy, "exit": p}
    return {
        "expected_ce": expected,
        "exit_entropy": -outputs["exit_entropy_weight"] * mean(entropy),
        LOSS_OBSERVED: {
            f"{kind}_{t + 1}": mean(seen[kind][t])
            for t in range(p.shape[0]) for kind in seen
        },
    }


def loss_parts(labels, outputs) -> dict:
    """The loss by its named parts; ``loss`` is their sum.  ``main``: the
    next token's mean cross-entropy.  ``mtp`` (a training forward with
    ``mtp_depth``): ``mtp_weight`` times the mean over the modules of
    ``L_k``, the cross-entropy of token ``i + k + 1`` summed over the
    ``T - k`` positions of a row that have one in ``labels`` and divided
    by ``T`` (arXiv:2412.19437, eqs. 24, 25).  A looped model's training
    forward: ``looped_parts``.  Each a mean of per-row terms, so
    ``trainer/step.py::weighted_mean_loss`` masks padded rows."""
    parts = looped_parts(labels, outputs)
    if parts is not None:
        return parts
    if not isinstance(outputs, dict):
        return {
            "main": softmax_cross_entropy_with_integer_labels(
                outputs, labels
            ).mean()
        }
    seq = labels.shape[1]
    modules = []
    for k, logits in enumerate(outputs["mtp_logits"], 1):
        per_token = softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(labels, -k, axis=1)
        )
        modules.append(
            jnp.where(jnp.arange(seq) < seq - k, per_token, 0.0).mean()
        )
    return {
        "main": softmax_cross_entropy_with_integer_labels(
            outputs["logits"], labels
        ).mean(),
        "mtp": outputs["mtp_weight"].mean() * sum(modules) / len(modules),
    }


def _terms(parts: dict):
    # (not ``sum``: its ``0 +`` would be one more op in every model's step)
    return functools.reduce(
        operator.add,
        (value for name, value in parts.items() if name != LOSS_OBSERVED),
    )


def loss(labels, outputs):
    return _terms(loss_parts(labels, outputs))


def _looped_loss(labels, outputs, weights):
    parts = looped_parts(labels, outputs, weights)
    return None if parts is None else _terms(parts)


loss.parts = loss_parts
# (the outputs of a looped model's training forward hold the head's weight,
# which is no row's, and the head's gradient is formed where a token's weight
# in the mean has to be known: what weighted_mean_loss asks first)
loss.weighted_mean = _looped_loss
loss_parts.weighted_mean = looped_parts


def optimizer(lr=3e-3):
    return optax.adam(lr)


def dataset_fn(dataset, mode, metadata):
    def _parse(record):
        ex = decode_example(record)
        tokens = ex["tokens"].astype(np.int32)
        feats = {"tokens": tokens[:-1]}
        if mode == Modes.PREDICTION:
            return feats
        return feats, tokens[1:]

    return dataset.map(_parse)


def eval_metrics_fn():
    return {"accuracy": Accuracy()}


def generate(
    params,
    prompt,
    num_steps: int,
    model: TransformerLM | None = None,
    temperature: float = 0.0,
    top_k: int = 0,
    rng=None,
    **model_kwargs,
):
    """Autoregressive generation with KV caches.

    params: trained parameters (from any of the training runtimes — the
    decode model shares the exact parameter structure).
    prompt: (batch, prompt_len) int tokens.
    temperature: <= 0 decodes greedily; > 0 samples from
        softmax(logits / temperature), optionally truncated to the
        ``top_k`` most likely tokens (0 = no truncation).  Sampling
        needs ``rng`` (a jax PRNG key).
    Returns (batch, prompt_len + num_steps) tokens.

    Each step feeds ONE token: the per-layer KV caches make a step
    O(seq) instead of O(seq^2) — this is the inference-side payoff of
    ``num_kv_heads`` (the cache shrinks by the GQA group factor).
    """
    if model is not None and model_kwargs:
        raise ValueError(
            "pass either a model or model_kwargs, not both "
            f"(got model + {sorted(model_kwargs)})"
        )
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs an rng key")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    prompt = jnp.asarray(prompt, jnp.int32)
    batch, prompt_len = prompt.shape
    max_len = prompt_len + num_steps
    base = model or TransformerLM(**model_kwargs)
    decode_model = base.clone(decode=True, max_decode_len=max_len)

    # empty caches from shapes only — no throwaway parameter init
    cache_shapes = jax.eval_shape(
        lambda: decode_model.init(
            jax.random.PRNGKey(0),
            {"tokens": jnp.zeros((batch, 1), jnp.int32)},
        )["cache"]
    )
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
    )

    def _select(logits, key):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        scaled = logits.astype(jnp.float32) / temperature
        if top_k:
            # clamp to the vocab; lax.top_k is O(V) vs a full sort
            kth = jax.lax.top_k(
                scaled, min(top_k, scaled.shape[-1])
            )[0][:, -1:]
            scaled = jnp.where(scaled >= kth, scaled, -1e30)
        return jax.random.categorical(key, scaled, axis=-1)

    @jax.jit
    def step(params, cache, token, key):
        logits, mutated = decode_model.apply(
            {"params": params, "cache": cache},
            {"tokens": token},
            mutable=["cache"],
        )
        return mutated["cache"], _select(logits[:, -1], key)

    n_keys = prompt_len + num_steps
    keys = (
        jax.random.split(rng, n_keys)
        if rng is not None
        # greedy never consults the key; any constant keeps step's
        # signature uniform
        else [jax.random.PRNGKey(0)] * n_keys
    )
    next_token = None
    for i in range(prompt_len):  # prefill one token at a time
        cache, next_token = step(
            params, cache, prompt[:, i : i + 1], keys[i]
        )
    out = [prompt[:, i] for i in range(prompt_len)]
    for i in range(num_steps):
        out.append(next_token)
        if i < num_steps - 1:  # the final step's forward would be unused
            cache, next_token = step(
                params, cache, next_token[:, None], keys[prompt_len + i]
            )
    return jnp.stack(out, axis=1)
