"""The comparison with the plain reference that joins ``correct``.

A configuration file's ``reference`` group names a module under
``references/`` (found by name, ``manifest.Cell.reference``) whose one
function ``loss_and_grads(params, features, labels)`` is the configuration's
mathematics in plain float32.  The system's side is the model the cell
trains, from the layer that owns it and its mesh
(``MeasuredExecutor.model_loss_and_grads``): its ``apply`` in the
configuration's dtype through its normal kernels, in training mode, the
model module's ``loss``, under ``jax.value_and_grad``, at the trainer's
parameters as the window left them, on a sample of the cell's own record
kind drawn from ``--seed`` (a stream of its own: records the job never
read).  Both limits of the group's ``tolerance`` are held.

It runs in the traced run only, after the window has closed and the
per-layer metrics have been read, and its programs go to a compile cache of
their own, so no end-to-end metric and no set-up counter holds its compile
or its memory, and no step program is pushed out of the cache ``setup_s``
is measured with.  What it cannot see is the step's plumbing around the
model: the vmapped ``weighted_mean_loss``, the stacked dispatch and the
optimizer update (PERF.md, section 2)."""

from __future__ import annotations

import contextlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from perf import trafficgen
from perf.manifest import ROOT

# the stream of ``--seed`` the sample is drawn from: the shards take 0..n-1
SAMPLE_STREAM = 1 << 20
# where the comparison's two programs a cell are kept (git-ignored, a fixed
# path inside the checkout): not in the step programs' cache, which a
# machine may cap and evict from
COMPILE_CACHE_DIR = os.path.join(ROOT, "perf", ".data", "reference_compile_cache")


def sample_rows(cell) -> int:
    """Records in the sample: ``reference.sample.units`` of the
    configuration's work unit (tokens, records), at least one record, a
    whole number a chip."""
    units = int(cell.config["reference"]["sample"]["units"])
    per_record = trafficgen.units_per_record(
        cell.record_kind(), cell.traffic, cell.config["work"]["unit"]
    )
    rows = max(1, units // per_record)
    return -(-rows // cell.chips) * cell.chips


def draw_sample(cell, seed: int):
    return trafficgen.one_batch(
        cell.record_kind(), cell.traffic, sample_rows(cell), seed, SAMPLE_STREAM
    )


@contextlib.contextmanager
def own_compile_cache():
    """The persistent compile cache switched to ``COMPILE_CACHE_DIR`` for
    what compiles inside, and back afterwards."""
    step_programs = jax.config.jax_compilation_cache_dir
    compilation_cache.set_cache_dir(COMPILE_CACHE_DIR)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        compilation_cache.set_cache_dir(step_programs)
        compilation_cache.reset_cache()


def _norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree)
        )
    )


@jax.jit
def errors(loss_sys, grads_sys, loss_ref, grads_ref) -> dict:
    """``|l_s - l_r| / |l_r|``, ``|g_s - g_r| / |g_r|`` over the whole tree
    and over each top-level block of it."""
    diff = jax.tree_util.tree_map(
        lambda s, r: s.astype(jnp.float32) - r, grads_sys, grads_ref
    )
    return {
        "loss_err": jnp.abs(loss_sys - loss_ref) / jnp.abs(loss_ref),
        "grad_err": _norm(diff) / _norm(grads_ref),
        "by_block": {k: _norm(diff[k]) / _norm(grads_ref[k]) for k in diff},
    }


def limits(group: dict) -> dict:
    """The group's two limits; both are numbers, and both are held."""
    return {name: float(group["tolerance"][name]) for name in ("loss", "grad")}


def compare(cell, executor, seed: int):
    """The ``reference`` entry of the info line; None for a configuration
    that names no reference."""
    module = cell.reference()
    if module is None:
        return None
    started = time.perf_counter()
    group = cell.config["reference"]
    tolerance = limits(group)
    features, labels = draw_sample(cell, seed)
    with own_compile_cache():
        params, loss_sys, grads_sys = executor.model_loss_and_grads(features, labels)
        loss_ref, grads_ref = jax.jit(module.loss_and_grads)(params, features, labels)
        got = jax.device_get(errors(loss_sys, grads_sys, loss_ref, grads_ref))
    report = {
        "loss_sys": float(loss_sys),
        "loss_ref": float(loss_ref),
        "loss_err": float(got["loss_err"]),
        "grad_err": float(got["grad_err"]),
        "by_block": {k: float(v) for k, v in got["by_block"].items()},
        "tolerance": tolerance,
        # what the limits were shown not to separate from a sound run
        "does_not_cover": group["does_not_cover"],
        "sample": {"records": int(np.shape(labels)[0]), "seed": seed},
    }
    # a NaN compares false
    report["agrees"] = all(
        report[name + "_err"] <= limit for name, limit in tolerance.items()
    )
    report["seconds"] = time.perf_counter() - started
    return report
