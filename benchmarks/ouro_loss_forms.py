"""The looped loss in its two forms at ONE state: does forming the head's
gradient beside the logits (``models/long_seq_transformer.py``'s
``exits_cross_entropy``, PR 57) move the loss VALUE, or any gradient, against
the plain composition it replaced (``plain_parts`` here: a pass's logits and
their cross-entropy under ``jax.checkpoint`` inside ``jax.lax.map``, the form
up to PR 55)?

Two trees' traced runs cannot say: each reaches a state of its own in 17
steps, and ``loss_err`` is a small difference of two close numbers there.
Here both forms read the same parameters and the same records in one process
on the chip, at the seeded init and at the state a window of
``ouro_2p6b_seq4096x2`` leaves:

    python benchmarks/ouro_loss_forms.py --workload ouro_2p6b_seq4096x2 \\
        --seed 2099465378 --seconds 10

runs ``perf/run.py --trace 1`` with these arguments and, in its comparison's
place, the sound comparison (the info line's ``reference`` as ever, so the
run's ``correct`` means what it always means) with ``reference.forms`` beside
it, a state a key:

- ``model``: the whole model's loss and parameter gradients on the
  comparison's one-record sample through either form: the shipped one held to
  the plain float32 reference as ``perf/reference.py`` holds the program
  (``loss_err``, ``grad_err``, ``by_block``), the plain form's ``loss_err``
  beside it, and the two forms against each other (``loss_rel``, and
  ``grads_rel``, the gradients' relative difference by block);
- ``exits``: the model's training outputs on that sample taken once and
  handed to both forms: the two values, the exit states' gradients compared
  bit for bit, the gates' and the head's by norm;
- ``step``: the same on a batch of the step's own rows under the row weights
  the step hands ``weighted_mean_loss``.

A builder's tool, never a ledger number (nothing under ``perf/`` imports
this).  ``tests/test_looped_lm.py`` holds the shipped form to ``plain_parts``
at a CPU's size."""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def plain_parts(labels, outputs, weights=None):
    """The looped loss as a plain composition, by its parts: the rows' terms,
    then the step's weighted mean (the plain mean without ``weights``)."""
    from elasticdl_tpu.models.long_seq_transformer import exit_distribution
    from elasticdl_tpu.telemetry.router_load import LOSS_OBSERVED
    from elasticdl_tpu.trainer.losses import (
        softmax_cross_entropy_with_integer_labels,
    )

    head = outputs["head"]

    @jax.checkpoint
    def exit_cross_entropy(state):
        logits = state @ head["kernel"].astype(state.dtype)
        if "bias" in head:
            logits = logits + head["bias"].astype(state.dtype)
        return softmax_cross_entropy_with_integer_labels(logits, labels)

    cross_entropy = jax.lax.map(exit_cross_entropy, outputs["exit_states"])
    log_p = exit_distribution(outputs["exit_gates"])
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)

    def mean(per_row):
        if weights is None:
            return per_row.mean()
        w = weights.astype(per_row.dtype)
        return jnp.sum(w * per_row) / jnp.maximum(jnp.sum(w), 1.0)

    seen = {"ce": cross_entropy, "exit": p}
    return {
        "expected_ce": mean(jnp.sum(p * cross_entropy, axis=0).mean(axis=-1)),
        "exit_entropy": mean(
            -outputs["exit_entropy_weight"] * entropy.mean(axis=-1)
        ),
        LOSS_OBSERVED: {
            f"{kind}_{t + 1}": mean(seen[kind][t].mean(axis=-1))
            for t in range(p.shape[0]) for kind in seen
        },
    }


def shipped_parts(labels, outputs, weights=None):
    """The same through the model module's own loss, as the step asks it."""
    from elasticdl_tpu.models import long_seq_transformer as zoo
    from elasticdl_tpu.trainer.step import weighted_mean_loss

    if weights is None:
        return zoo.loss_parts(labels, outputs)
    return weighted_mean_loss(zoo.loss_parts, labels, outputs, weights)


FORMS = {"shipped": shipped_parts, "plain": plain_parts}
EXITS = ("exit_states", "exit_gates", "head")


def total(parts):
    from elasticdl_tpu.telemetry.router_load import LOSS_OBSERVED

    return sum(v for k, v in parts.items() if k != LOSS_OBSERVED)


def _relative(got, want) -> float:
    from perf import reference

    diff = jax.tree_util.tree_map(
        lambda g, w: g.astype(jnp.float32) - w.astype(jnp.float32), got, want
    )
    return float(reference._norm(diff) / reference._norm(want))


def exits_report(labels, outputs, weights=None) -> dict:
    """Both forms on one set of training outputs: the values, and the
    gradients in the exit states, the gates and the head."""
    rest = {k: v for k, v in outputs.items() if k not in EXITS}
    read = {
        name: jax.jit(jax.value_and_grad(
            lambda exits, form=form: total(form(labels, {**rest, **exits}, weights))
        ))({k: outputs[k] for k in EXITS})
        for name, form in FORMS.items()
    }
    (loss, grads), (loss_plain, grads_plain) = read["shipped"], read["plain"]
    states, states_plain = grads["exit_states"], grads_plain["exit_states"]
    differ = states != states_plain
    return {
        "loss": {name: float(value) for name, (value, _) in read.items()},
        "loss_rel": abs(float(loss) - float(loss_plain)) / abs(float(loss_plain)),
        "states_dtype": str(states.dtype),
        "states_elements": int(states.size),
        "states_elements_that_differ": int(jnp.sum(differ)),
        "states_rel": _relative(states, states_plain),
        "gates_rel": _relative(grads["exit_gates"], grads_plain["exit_gates"]),
        "head_rel": {
            k: _relative(grads["head"][k], grads_plain["head"][k])
            for k in grads["head"]
        },
    }


def compare_forms(cell, executor, seed: int, control: bool = False):
    """``perf/reference.py::compare``'s sound report, and under ``forms``
    both forms of the loss at the window's state and at the seeded init."""
    from elasticdl_tpu.ops.attention import attention_mesh_scope
    from perf import reference, trafficgen

    started = time.perf_counter()
    module, group = cell.reference(), cell.config["reference"]
    tolerance = reference.limits(group)
    features, labels = reference.draw_sample(cell, seed)
    executor.release_optimizer_state()
    trainer, model = executor._trainer, executor._model
    state = trainer.state
    step_rows = trafficgen.plan(cell.traffic, cell.chips)["minibatch_size"]
    step_features, step_labels = trafficgen.one_batch(
        cell.record_kind(), cell.traffic, step_rows, seed,
        reference.SAMPLE_STREAM + 1,
    )
    rngs = {"dropout": jax.random.PRNGKey(0)}

    def outputs_of(params, features):
        outputs, _ = state.apply_fn(
            {"params": params, **state.model_state}, features, training=True,
            mutable=list(state.model_state), rngs=rngs,
        )
        return outputs

    def through(form):
        return jax.jit(jax.value_and_grad(
            lambda params, features, labels: total(
                form(labels, outputs_of(params, features))
            ).astype(jnp.float32)
        ))

    plain_reference = jax.jit(module.loss_and_grads)

    def at(params):
        # one gradient tree beside the one being made, as the comparison
        # itself holds them: the reference's leaves before the plain form's
        # arrives, which is held to the shipped one's
        placed = trainer.place_batch(features), trainer.place_batch(labels)
        loss_ref, grads_ref = plain_reference(params, features, labels)
        loss, grads = through(shipped_parts)(params, *placed)
        got = jax.device_get(reference.errors(loss, grads, loss_ref, grads_ref))
        del grads_ref
        loss_plain, grads_plain = through(plain_parts)(params, *placed)
        loss, loss_plain, loss_ref = float(loss), float(loss_plain), float(loss_ref)
        model_report = {
            "loss_ref": loss_ref,
            "loss": {"shipped": loss, "plain": loss_plain},
            "loss_rel": abs(loss - loss_plain) / abs(loss_plain),
            "loss_err": {
                "shipped": float(got["loss_err"]),
                "plain": abs(loss_plain - loss_ref) / abs(loss_ref),
            },
            "grad_err": float(got["grad_err"]),
            "by_block": {k: float(v) for k, v in got["by_block"].items()},
            "grads_rel": {
                "all": _relative(grads, grads_plain),
                **{k: _relative(grads[k], grads_plain[k]) for k in grads_plain},
            },
        }
        del grads, grads_plain
        report = {"model": model_report}
        for name, (f, l, weights) in {
            "exits": (features, labels, None),
            "step": (
                step_features, step_labels,
                jnp.ones((step_rows,), jnp.float32),
            ),
        }.items():
            f, l = trainer.place_batch(f), trainer.place_batch(l)
            report[name] = exits_report(
                l, jax.jit(outputs_of)(params, f), weights
            )
        return report

    with reference.own_compile_cache(), trainer.mesh, attention_mesh_scope(
        trainer.mesh
    ):
        forms = {"window": at(state.params)}
        sound = forms["window"]["model"]
        # the seeded init, made again beside the window's parameters (the
        # optimizer's moments have left the device)
        init = jax.jit(
            lambda: model.init(
                jax.random.PRNGKey(0), features, training=False
            )["params"],
            out_shardings=trainer.state_shardings.params,
        )()
        forms["init"] = at(init)
    report = {
        "loss_sys": sound["loss"]["shipped"],
        "loss_ref": sound["loss_ref"],
        "loss_err": sound["loss_err"]["shipped"],
        "grad_err": sound["grad_err"],
        "by_block": sound["by_block"],
        "tolerance": tolerance,
        "sample": {"records": int(labels.shape[0]), "seed": seed},
        "forms": forms,
    }
    report["agrees"] = all(
        report[name + "_err"] <= limit for name, limit in tolerance.items()
    )
    report["seconds"] = time.perf_counter() - started
    return report


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from perf import reference, run

    reference.compare = compare_forms
    argv = list(sys.argv[1:] if argv is None else argv)
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
