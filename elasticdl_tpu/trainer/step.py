"""Jitted step builders: train / evaluate / predict.

Reference: the worker's training step is a ``tf.function`` GradientTape over
``model.call`` followed by a gRPC gradient push (``worker.py:646-669`` +
``:444-530``).  The TPU build fuses all of it — forward, loss, backward,
optimizer update and (under a mesh) the gradient all-reduce — into a single
XLA program: with ``jax.jit`` over dp-sharded batches and replicated
parameters, GSPMD inserts the psum over ICI automatically, so the same step
function serves single-chip Local runs and multi-host meshes.

No data-dependent Python control flow exists inside the step; retries and
task accounting live outside (host side), mirroring the reference's split
between minibatch compute and control.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from elasticdl_tpu.telemetry.router_load import LOSS_OBSERVED, LOSS_PARTS
from elasticdl_tpu.trainer.state import TrainState


def _apply(state: TrainState, params, features, training: bool):
    """Run the model, handling mutable collections (batch_stats).

    Training forwards get a ``dropout`` rng folded from the step counter:
    deterministic per step (replay/restore-safe, identical across replicas
    of an SPMD step) yet fresh every step.
    """
    variables = {"params": params, **state.model_state}
    if training:
        rngs = {
            "dropout": jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        }
        if state.model_state:
            outputs, new_state = state.apply_fn(
                variables,
                features,
                training=True,
                mutable=list(state.model_state),
                rngs=rngs,
            )
            return outputs, new_state
        outputs = state.apply_fn(variables, features, training=True, rngs=rngs)
        return outputs, state.model_state
    outputs = state.apply_fn(variables, features, training=False)
    return outputs, state.model_state


def _cast_floats(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
        else x,
        tree,
    )


def weighted_mean_loss(loss_fn, labels, outputs, weights):
    """``sum(w_i * loss_i) / sum(w_i)`` with per-row losses obtained by
    vmapping ``loss_fn`` over singleton batches.

    This is THE mask semantics of shape-canonical batching
    (docs/designs/shape_canonicalization.md): rows with weight 0 (the
    padding ``pad_to`` appends to reach the canonical batch shape)
    contribute exactly zero to THIS loss and therefore exactly zero
    gradient through it — unlike the old repeat-last-row padding, which
    silently over-weighted the repeated row.  For a ``loss_fn`` that is
    a mean of independent per-row terms (every zoo loss is), an all-ones
    weight vector reproduces ``loss_fn(labels, outputs)`` exactly up to
    reduction order.

    Scope: the exactness claim covers the primary loss path only.
    Batch-composition-dependent terms — sown auxiliary losses (MoE load
    balancing, regularizers; added to the total in ``forward_loss``) and
    batch statistics (BatchNorm) — still observe the padded fill rows of
    a tail batch, as they did under the legacy divisor padding (the
    canonical shape pads further; see the design doc's limits section).

    Cost rule: a ``loss_fn`` that picks the label's entry with a gather
    (``take_along_axis``, optax's integer-label cross-entropy) transposes,
    under this ``vmap``, into a scatter over the flattened logits plus two
    layout-copy loops — in the GPT-2-small step a 1.65 GB flat f32 buffer
    and 44 ms of a 109 ms step (PERF.md, PR 25).  Use the gather-free functions of
    :mod:`elasticdl_tpu.trainer.losses`, as every zoo loss does.
    """

    def one_row(labels_row, outputs_row):
        labels_1 = jax.tree_util.tree_map(lambda x: x[None], labels_row)
        outputs_1 = jax.tree_util.tree_map(lambda x: x[None], outputs_row)
        return loss_fn(labels_1, outputs_1)

    def mean(per_row):
        w = weights.astype(per_row.dtype)
        # max(sum, 1) guards the (never-dispatched) all-zero mask; a real
        # dispatch always carries >= 1 real row
        return jnp.sum(w * per_row) / jnp.maximum(jnp.sum(w), 1.0)

    # a ``loss_fn`` whose model's outputs hold something that is no row's (a
    # looped model's head, whose gradient the loss forms beside the logits,
    # at the rows' weights) takes them itself: ``weighted_mean``, else None
    own = getattr(loss_fn, "weighted_mean", None)
    reduced = own(labels, outputs, weights) if own is not None else None
    if reduced is not None:
        return reduced
    # (a ``loss_fn`` that returns its loss by named parts gets each weighted)
    return jax.tree_util.tree_map(mean, jax.vmap(one_row)(labels, outputs))


_DONATION_WARNING_PATTERN = "Some donated buffers were not usable"


def _silence_unusable_donation_warning():
    """Batch donation is BEST-EFFORT by design: XLA aliases a donated
    batch into an output only when shapes/layouts permit and frees it
    early otherwise — on small models nothing aliases and jax warns per
    compile, so opting in makes that warning noise, not news.  The
    filter installs at most one live entry: repeated trainer builds
    (bench runs many configs per process) must not accumulate
    duplicates, and the presence CHECK (rather than a module latch)
    keeps it working after a ``catch_warnings`` block reset the global
    filter list.  Scope caveat: the filter is process-global, so it
    also mutes the same warning for state-only trainers built later —
    accepted, since state donation aliases by construction and has
    never fired it."""
    import warnings

    for entry in warnings.filters:
        if (
            entry[0] == "ignore"
            and getattr(entry[1], "pattern", None)
            == _DONATION_WARNING_PATTERN
        ):
            return
    warnings.filterwarnings(
        "ignore", message=_DONATION_WARNING_PATTERN
    )


def build_train_step(
    loss_fn: Callable,
    compute_dtype=None,
    remat: bool = False,
    donate: bool = True,
    extra_grad_fn: Callable | None = None,
    state_shardings=None,
    device_parse: Callable | None = None,
    donate_batch: bool = False,
) -> Callable:
    """Build ``(state, features, labels[, weights]) -> (state, step_metrics)``.

    loss_fn: the model module's ``loss(labels, predictions)``.
    weights: optional per-row ``(batch,)`` sample weights — the loss
        becomes :func:`weighted_mean_loss`, so rows canonical-shape
        padding appended (weight 0) contribute zero gradient.  Omitting
        it (``None``) keeps the reference semantics bit-for-bit; the two
        call patterns are distinct jit cache entries, and the runtimes
        always pass a weight vector so they hold exactly one.
    donate_batch: extend donation from state-only to the batch and mask
        buffers (``--device_prefetch``, trainer/device_pipeline.py): a
        batch is dead after its dispatch, so XLA reuses its memory for
        outputs and steady-state dispatches allocate no fresh device
        buffers.  Callers must treat placed batch arrays as consumed —
        a read after the dispatch raises on the deleted Array.
    compute_dtype: cast float inputs (e.g. bfloat16) before the forward;
        parameters and optimizer state stay float32 (mixed precision on the
        MXU without loss-scale bookkeeping, since bf16 keeps fp32 range).
    remat: wrap the forward in ``jax.checkpoint`` to trade FLOPs for HBM.
    extra_grad_fn: optional hook ``(grads, state) -> grads`` (gradient
        clipping etc. normally belongs in the optax chain instead).
    state_shardings: optional sharding pytree matching the TrainState; when
        given, the updated state is pinned to the same mesh layout (the
        SPMD path) — this is the ONE step builder both LocalExecutor and
        SPMDTrainer share, so their step semantics cannot drift.
    device_parse: optional model hook run INSIDE the jitted step before
        the forward (and before compute_dtype casting): elementwise
        decode/normalization of compact wire dtypes (e.g. uint8 images
        -> f32/255), so the host->device transfer ships the small form.
    """

    def forward_loss(params, state, features, labels, weights):
        # the regions of the step that no module names carry a
        # ``jax.named_scope``, named here, which telemetry/op_scopes.py
        # reads back as the op's part: a scope changes an op's metadata and
        # nothing else of the compiled program
        with jax.named_scope("parse"):
            if device_parse is not None:
                features = device_parse(features)
            features = _cast_floats(features, compute_dtype)
        outputs, new_model_state = _apply(state, params, features, True)
        # a model that declares the LOSS_PARTS collection (a second-token
        # loss beside the main one) has its loss computed by the named
        # parts ``loss_fn.parts`` gives; they leave the step inside the
        # state, for telemetry/router_load.py to read on demand
        by_parts = LOSS_PARTS in new_model_state
        fn = loss_fn.parts if by_parts else loss_fn
        with jax.named_scope("loss"):
            if weights is None:
                loss = fn(labels, outputs)
            else:
                loss = weighted_mean_loss(fn, labels, outputs, weights)
            # layer-contributed losses (MoE load balancing, regularizers):
            # any value sown into the "losses" collection joins the training
            # loss — the reference adds Keras model reg losses the same way
            # (worker.py:656-669); where the loss goes by parts, as the part
            # of its own name (the layers' summed)
            sown = jax.tree_util.tree_leaves_with_path(
                new_model_state.get("losses", {})
            )
            if by_parts:
                # (what the loss function saw and is no term of the loss)
                observed = loss.pop(LOSS_OBSERVED, None)
                if observed is not None:
                    new_model_state = {
                        **new_model_state,
                        LOSS_OBSERVED: jax.lax.stop_gradient(observed),
                    }
                for path, leaf in sown:
                    name = path[-1].key
                    loss[name] = loss.get(name, 0.0) + jnp.sum(leaf)
                new_model_state = {
                    **new_model_state,
                    LOSS_PARTS: jax.lax.stop_gradient(
                        {k: v.astype(jnp.float32) for k, v in loss.items()}
                    ),
                }
                loss = sum(loss.values())
            else:
                for _, leaf in sown:
                    loss = loss + jnp.sum(leaf)
            return loss.astype(jnp.float32), (outputs, new_model_state)

    if remat:
        forward_loss = jax.checkpoint(
            forward_loss, static_argnums=(), policy=None
        )

    def train_step(state: TrainState, features, labels, weights=None):
        grad_fn = jax.value_and_grad(forward_loss, has_aux=True)
        (loss, (_, new_model_state)), grads = grad_fn(
            state.params, state, features, labels, weights
        )
        with jax.named_scope("optimizer"):
            if extra_grad_fn is not None:
                grads = extra_grad_fn(grads, state)
            new_state = state.apply_gradients(grads)
        new_state = new_state.replace(model_state=new_model_state)
        return new_state, {"loss": loss}

    donate_argnums = (0,) if donate else ()
    if donate_batch:
        donate_argnums = donate_argnums + (1, 2, 3)
        _silence_unusable_donation_warning()
    return jax.jit(
        train_step,
        donate_argnums=donate_argnums,
        out_shardings=None
        if state_shardings is None
        else (state_shardings, None),
    )


def build_eval_step(
    loss_fn: Callable | None = None,
    device_parse: Callable | None = None,
) -> Callable:
    """Build ``(state, features, labels[, weights]) ->
    outputs_or_(outputs, loss)``.

    Outputs are returned to the host and reported to the master for metric
    accumulation (reference worker.py:552-565 report_evaluation_metrics) —
    metrics themselves never run on device.  With per-row ``weights`` the
    returned loss is :func:`weighted_mean_loss` — exact over the REAL
    rows of a canonical-shape batch, so callers need no host-side loss
    recompute for padded tails.
    """

    def eval_step(state: TrainState, features, labels, weights=None):
        if device_parse is not None:
            features = device_parse(features)
        outputs, _ = _apply(state, state.params, features, False)
        if loss_fn is None:
            return outputs
        if weights is None:
            return outputs, loss_fn(labels, outputs)
        return outputs, weighted_mean_loss(loss_fn, labels, outputs, weights)

    return jax.jit(eval_step)


def build_predict_step(device_parse: Callable | None = None) -> Callable:
    def predict_step(state: TrainState, features):
        if device_parse is not None:
            features = device_parse(features)
        outputs, _ = _apply(state, state.params, features, False)
        return outputs

    return jax.jit(predict_step)


def resolve_optimizer(spec_optimizer, learning_rate: float | None = None):
    """The model module's ``optimizer`` export is either an optax
    ``GradientTransformation`` or a factory ``(lr=...) -> transformation``
    (the reference's contract returns a Keras optimizer,
    ``model_utils.py:94-150``)."""
    import optax

    if isinstance(spec_optimizer, optax.GradientTransformation):
        return spec_optimizer
    if callable(spec_optimizer):
        try:
            if learning_rate is not None:
                return spec_optimizer(lr=learning_rate)
            return spec_optimizer()
        except TypeError:
            return spec_optimizer()
    raise TypeError(
        f"optimizer spec must be an optax transformation or factory, got "
        f"{type(spec_optimizer)!r}"
    )
