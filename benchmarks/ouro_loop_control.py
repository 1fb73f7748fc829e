"""Faults of a looped model's LOOP alone, planted in the plain reference
(``perf/references/ouro.py``) and read through the benchmark's own comparison
at the state a window of ``ouro_2p6b_seq4096x2`` leaves: can ``correct`` see
the second to fourth pass?

``perf/run.py --control`` puts the reference with float8 weights in the
program's place, a fault of every weight that the first pass alone would
show.  The faults here leave the first pass as it is (``FAULTS``): the later
passes' weight gradient dropped (a backward carry that keeps one pass's), the
next pass reading the state before the norm, an exit distribution that
forgets who already left.  Each goes where the float8 weights go: the faulty
reference in the program's place, the sound reference beside it, the errors
``perf/reference.py::errors`` forms, held to the configuration's limits.
Every one has to read ``agrees`` false.

A builder's tool, never a ledger number (``perf/run.py`` is the benchmark;
nothing under ``perf/`` imports this).  One process, one window, one state:

    python benchmarks/ouro_loop_control.py --workload ouro_2p6b_seq4096x2 \\
        --seed 3100000007 --seconds 10

runs ``perf/run.py --trace 1`` with these arguments and, in its comparison's
place, the sound comparison (the info line's ``reference`` as ever, so the
run's ``correct`` means what it always means) followed by the float8 control
and every fault at the same state and sample, under ``reference.controls``;
and under ``reference.passes`` the four ``CE_t`` and the mean exit
distribution of the compared state on the sample, in the reference's
arithmetic, beside the newest train step's (``router_load.read_exits``).
``tests/test_looped_lm.py`` holds each fault to be one at a CPU's size.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def plain_reference():
    """A copy of the reference module of its own, to plant a fault in."""
    spec = importlib.util.spec_from_file_location(
        "reference_ouro_copy", os.path.join(ROOT, "perf", "references", "ouro.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _later_passes_weight_gradient_dropped(module):
    """The blocks' weights get the first pass's gradient alone (what a
    backward scan whose carry is overwritten, not added to, leaves of the
    last pass it ran): the activations' gradient flows as it should."""

    def exits(params, tokens):
        depth = sum(name.startswith("block_") for name in params)
        h = params["tok_embed"]["embedding"][tokens]
        reached = []
        for step in range(module.TOTAL_UT_STEPS):
            for index in range(depth):
                weights = params[f"block_{index}"]
                if step:
                    weights = jax.lax.stop_gradient(weights)
                h = jax.checkpoint(module.layer)(h, weights)
            h = module.rms_norm(h, params["RMSNorm_0"])
            reached.append((h, module.exit_gate(h, params["exit_gate"])))
        return reached

    module.exits = exits


def _next_pass_reads_the_state_before_the_norm(module):
    def exits(params, tokens):
        depth = sum(name.startswith("block_") for name in params)
        h = params["tok_embed"]["embedding"][tokens]
        reached = []
        for _ in range(module.TOTAL_UT_STEPS):
            for index in range(depth):
                h = jax.checkpoint(module.layer)(h, params[f"block_{index}"])
            normed = module.rms_norm(h, params["RMSNorm_0"])
            reached.append((normed, module.exit_gate(normed, params["exit_gate"])))
        return reached

    module.exits = exits


def _exit_distribution_forgets_who_left(module):
    """``p_t = g_t (1 - g_1)`` for every later pass, in place of the product
    over all the passes before: the first two passes' shares are right."""

    def distribution(gates):
        stayed = 1.0 - gates[0]
        return [gates[0]] + [g * stayed for g in gates[1:-1]] + [stayed]

    module.exit_distribution = distribution


FAULTS = {
    "later_passes_weight_gradient_dropped": _later_passes_weight_gradient_dropped,
    "next_pass_reads_the_state_before_the_norm": _next_pass_reads_the_state_before_the_norm,
    "exit_distribution_forgets_who_left": _exit_distribution_forgets_who_left,
}


def faulty(name):
    """``loss_and_grads`` of a reference with the fault ``name`` planted."""
    module = plain_reference()
    FAULTS[name](module)
    return module.loss_and_grads


def passes(module, params, features, labels) -> dict:
    """Each pass's mean cross-entropy and mean exit probability on a sample,
    in the reference's arithmetic."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        reached = module.exits(params, tokens)
        p = module.exit_distribution([g for _, g in reached])
        return {
            "cross_entropy": [
                module.token_losses(h, params["lm_head"], labels).mean()
                for h, _ in reached
            ],
            "exit_distribution": [p_t.mean() for p_t in p],
        }


def compare_with_controls(cell, executor, seed: int, control: bool = False):
    """``perf/reference.py::compare``'s sound report, and beside it every
    control's at the same state and sample."""
    from elasticdl_tpu.telemetry import router_load
    from perf import reference

    started = time.perf_counter()
    module, group = cell.reference(), cell.config["reference"]
    tolerance = reference.limits(group)
    features, labels = reference.draw_sample(cell, seed)
    executor.release_optimizer_state()

    def read(loss, grads):
        got = jax.device_get(reference.errors(loss, grads, loss_ref, grads_ref))
        seen = {
            "loss": float(loss),
            "loss_err": float(got["loss_err"]),
            "grad_err": float(got["grad_err"]),
            "grad_norm": float(reference._norm(grads)),
            "by_block": {k: float(v) for k, v in got["by_block"].items()},
        }
        seen["agrees"] = all(
            seen[name + "_err"] <= limit for name, limit in tolerance.items()
        )
        return seen

    with reference.own_compile_cache():
        params, model_state, loss_sys, grads_sys = executor.model_loss_and_grads(
            features, labels
        )
        plain = jax.jit(module.loss_and_grads)
        loss_ref, grads_ref = plain(params, features, labels)
        sound = read(loss_sys, grads_sys)
        del grads_sys
        controls = {}
        planted = {"float8_weights": lambda: plain(
            reference.float8_weights(params), features, labels
        )}
        for name in FAULTS:
            planted[name] = lambda name=name: jax.jit(faulty(name))(
                params, features, labels
            )
        for name, run in planted.items():
            began = time.perf_counter()
            loss, grads = run()
            controls[name] = read(loss, grads)
            del grads
            controls[name]["seconds"] = time.perf_counter() - began
        on_sample = jax.device_get(
            jax.jit(lambda *a: passes(module, *a))(params, features, labels)
        )
    report = {
        "loss_sys": sound["loss"],
        "loss_ref": float(loss_ref),
        "loss_err": sound["loss_err"],
        "grad_err": sound["grad_err"],
        "grad_norm_ref": float(reference._norm(grads_ref)),
        "by_block": sound["by_block"],
        "tolerance": tolerance,
        "sample": {"records": int(labels.shape[0]), "seed": seed},
        "agrees": sound["agrees"],
        "controls": controls,
        "passes": {
            "on_the_sample": {
                k: [float(x) for x in v] for k, v in on_sample.items()
            },
            "newest_train_step": router_load.read_exits(model_state),
        },
    }
    report["seconds"] = time.perf_counter() - started
    return report


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from perf import reference, run

    reference.compare = compare_with_controls
    argv = list(sys.argv[1:] if argv is None else argv)
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
