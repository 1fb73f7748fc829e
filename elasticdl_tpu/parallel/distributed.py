"""The SPMD training engine: sharded state + compiled collective step.

This is the TPU-native replacement for the reference's whole data plane:

- ``pull_variable``/``push_gradient`` gRPC fan-out (worker.py:295-530) →
  nothing: parameters live on device, sharded or replicated per the rules;
  gradient reduction is a psum XLA inserts from the shardings.
- PS-side optimizer apply (ps/servicer.py:107-188) → ``optax`` update
  inside the same jitted step.
- FTLib allreduce (collective_ops/communicator.py) → the same psum.

One ``SPMDTrainer`` instance per worker process; the same code runs on a
1-device Local mesh and a multi-host pod slice.
"""

from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from elasticdl_tpu.ops.on_mesh import attention_mesh_scope
from elasticdl_tpu.parallel import elastic, program_store
from elasticdl_tpu.parallel import sharding as sharding_lib
from elasticdl_tpu.telemetry import op_scopes, router_load
from elasticdl_tpu.telemetry.anatomy import PHASE_H2D_TRANSFER, TIMELINE
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import (
    build_eval_step,
    build_predict_step,
    build_train_step,
)
from elasticdl_tpu.utils.constants import EMBEDDING_AUTO_DISTRIBUTE_BYTES

# Layout-invariant RNG: state is *created* sharded (init jitted with
# out_shardings below), and with non-partitionable threefry the
# partitioner does NOT preserve random bits across layouts — the same seed then inits different weights on dp=2,tp=2
# than on one device, breaking mesh-parity tests and cross-topology
# reproducibility.  Partitionable threefry makes random bits a pure
# function of (key, position), independent of the mesh.
jax.config.update("jax_threefry_partitionable", True)


class SPMDTrainer:
    def __init__(
        self,
        mesh: Mesh,
        model,
        loss_fn: Callable,
        tx,
        sample_features,
        rules: Sequence[sharding_lib.Rule] = (),
        compute_dtype=None,
        remat: bool = False,
        donate: bool = True,
        rng_seed: int = 0,
        embedding_threshold: int | None = EMBEDDING_AUTO_DISTRIBUTE_BYTES,
        device_parse: Callable | None = None,
        donate_batch: bool = False,
        job_identity: dict | None = None,
    ):
        """``embedding_threshold``: tables bigger than this many bytes are
        auto-distributed over the mesh (the reference's 2MB model-handler
        policy); pass ``None`` when a ModelHandler supplies the rules
        explicitly, so the policy has exactly one owner.

        ``donate_batch`` (``--device_prefetch``): batch/mask buffers are
        donated to the train-step dispatch alongside the state — a
        placed batch is consumed by its dispatch and must never be
        re-read (the device-pipeline staging layer enforces single-take
        ownership).  Lockstep worlds must agree on this setting: it is
        part of the compiled program, and the enabling env is
        master-forwarded so they always do.

        ``job_identity`` (``program_store.job_identity(args, module)``):
        what of the job is a constant of this trainer's programs.  With
        it, and with the program store switched on, the init program, the
        train step and the stacked scan are loaded from the store instead
        of traced (built and stored on a miss); without either, they are
        plain ``jit`` programs."""
        self.mesh = mesh

        sample_features = _host_slice_for_init(sample_features)

        def state_of(variables):
            params = variables.get("params", {})
            model_state = {
                k: v for k, v in variables.items() if k != "params"
            }
            return TrainState.create(model.apply, params, tx, model_state)

        def create_state():
            init_features = (
                device_parse(sample_features)
                if device_parse is not None
                else sample_features
            )
            return state_of(
                model.init(
                    jax.random.PRNGKey(rng_seed), init_features, training=False
                )
            )

        self._donate_batch = bool(donate_batch)
        store = program_store.active()
        self._programs = None
        if store is not None and job_identity is not None:
            self._programs = _StoredPrograms(
                store,
                mesh,
                {
                    **program_store.process_identity(job_identity, mesh),
                    # baked into the init program
                    "rng_seed": int(rng_seed),
                    "sample_features": [
                        [list(x.shape), str(x.dtype)]
                        for x in jax.tree_util.tree_leaves(sample_features)
                    ],
                },
            )
        # Shapes first (no FLOPs), then shard-aware materialization: the
        # state is *created* already laid out over the mesh, so no host
        # copy of a model bigger than one host's RAM is ever needed.
        # With the program store on, the variables' shapes come from its
        # note where it has one: tracing the model's init is then skipped
        # too (what is left traces the optimizer's init alone).
        state_shapes = None
        if self._programs is not None:
            state_shapes = self._programs.noted_state_shapes(state_of)
        if state_shapes is None:
            state_shapes = jax.eval_shape(create_state)
            if self._programs is not None:
                self._programs.note_state_shapes(state_shapes, state_of)
        if embedding_threshold is not None:
            from elasticdl_tpu.layers.embedding import auto_partition_rules

            rules = tuple(rules) + tuple(
                auto_partition_rules(
                    state_shapes.params, mesh, embedding_threshold
                )
            )
        self.state_specs = sharding_lib.infer_param_specs(
            state_shapes, mesh, rules
        )
        self.state_shardings = sharding_lib.specs_to_shardings(
            self.state_specs, mesh
        )
        if self._programs is not None:
            self._programs.identity["trainer"] = {
                "compute_dtype": str(compute_dtype),
                "remat": bool(remat),
                "donate": bool(donate),
                "donate_batch": self._donate_batch,
                "state_shardings": [
                    program_store.describe_sharding(s)
                    for s in jax.tree_util.tree_leaves(self.state_shardings)
                ],
            }
        init = jax.jit(create_state, out_shardings=self.state_shardings)
        with mesh, attention_mesh_scope(mesh):
            if self._programs is not None:
                # called once and dropped: a loaded program holds device
                # memory for as long as it lives
                init = self._programs.load_or_build(
                    "init", init, (), jax.tree_util.tree_structure(state_shapes)
                )
            self.state = init()
        # an expert model's router counts stay in the state as device
        # arrays; router_load.read() fetches the newest on demand
        router_load.watch(self)
        # ... and op_scopes.read() maps the train programs' ops to the
        # model's scopes, from the programs this trainer dispatched
        op_scopes.watch(self)
        self._jit_calls: dict = {}
        self._batch_shardings_cache: dict = {}
        self._stacked_scan_cache: dict = {}
        # mesh topology is immutable for this trainer's lifetime: resolve
        # the multi-process layout once, not per minibatch
        self._multiprocess = elastic.is_multiprocess_mesh(mesh)
        self._process_index = (
            elastic.my_process_index(mesh) if self._multiprocess else 0
        )
        self._local_range_cache: dict = {}

        # the SAME builders LocalExecutor uses (trainer/step.py) — the only
        # SPMD addition is pinning the updated state to the mesh layout
        self._train_step = build_train_step(
            loss_fn,
            compute_dtype=compute_dtype,
            remat=remat,
            donate=donate,
            state_shardings=self.state_shardings,
            device_parse=device_parse,
            donate_batch=self._donate_batch,
        )
        self._eval_step = build_eval_step(loss_fn, device_parse=device_parse)
        self._predict_step = build_predict_step(device_parse=device_parse)

    # ---- batch placement --------------------------------------------------

    def _batch_sharding(self, ndim: int) -> NamedSharding:
        if ndim not in self._batch_shardings_cache:
            # a mesh with sp > 1 means the user chose sequence
            # parallelism: dim 1 of every rank>=2 batch array is the
            # sequence dim (the framework layout convention) and shards
            # over sp; batch_sharding ignores sp_dim on sp=1 meshes
            self._batch_shardings_cache[ndim] = sharding_lib.batch_sharding(
                self.mesh, ndim, sp_dim=1 if ndim >= 2 else None
            )
        return self._batch_shardings_cache[ndim]

    def place_batch(self, tree):
        """Shard a host-global batch over the mesh's data axes.

        Single-process: a plain sharded device_put.  Multi-process mesh:
        every process passes the SAME host-global batch; each contributes
        the rows its devices own — no cross-host copy, and the global
        Array equals the host batch.  Row-range lookups are memoized per
        shape (pure functions of the immutable mesh/sharding).

        Every public ``place_*`` records ONE ``h2d_transfer`` span on the
        host's timeline (telemetry/anatomy.py) with the bytes it placed:
        here, where the work is, so every caller's is recorded.
        """
        t0 = time.perf_counter_ns()
        return _note_placed(t0, self._place_batch(tree))

    def _place_batch(self, tree):
        def _place(x):
            x = np.asarray(x)
            sh = self._batch_sharding(x.ndim)
            if not self._multiprocess:
                return jax.device_put(x, sh)
            cached = self._local_range_cache.get(x.shape)
            if cached is None:
                if elastic.dim0_split_only(sh, x.shape):
                    cached = elastic.local_batch_ranges(
                        sh, x.shape, self._process_index
                    )
                else:
                    cached = ()  # e.g. sp spans processes: split on dim 1+
                self._local_range_cache[x.shape] = cached
            if not cached:
                # universal path: every process holds the full host batch
                # (lockstep reads whole tasks), each device slices its
                # block — correct for ANY sharding layout
                return jax.make_array_from_callback(
                    x.shape, sh, lambda idx: x[idx]
                )
            local = np.concatenate([x[lo:hi] for lo, hi in cached], axis=0)
            return jax.make_array_from_process_local_data(
                sh, local, global_shape=x.shape
            )

        return jax.tree_util.tree_map(_place, tree)

    # ---- shape-canonical batching ------------------------------------------
    # THE canonical row count itself is a pure function of static config
    # (stacking.canonical_batch_rows over the mesh's batch divisor) —
    # the runtimes compute it at build time, before this trainer exists.

    def pad_to(self, tree, rows: int):
        """Pad the batch's leading dim to EXACTLY ``rows`` (repeating the
        last row; padded rows carry zero weight via :meth:`row_mask`, so
        the fill only has to be shape/dtype-valid, not meaningful)."""

        def _pad(x):
            x = np.asarray(x)
            n = x.shape[0]
            if n == rows:
                return x
            if n > rows:
                raise ValueError(
                    f"batch of {n} rows exceeds the canonical shape "
                    f"({rows} rows)"
                )
            return np.concatenate(
                [x, np.repeat(x[-1:], rows - n, axis=0)], axis=0
            )

        return jax.tree_util.tree_map(_pad, tree)

    def row_mask(self, n_real: int, rows: int) -> np.ndarray:
        """``(rows,)`` float32 sample weights: 1 for the real rows, 0 for
        the padding :meth:`pad_to` appended."""
        mask = np.zeros(rows, np.float32)
        mask[:n_real] = 1.0
        return mask

    def place_canonical(self, tree, rows: int):
        """pad_to + place_batch — THE canonical-shape feed all three
        runtimes use (one body, so their dispatch shapes cannot
        diverge); outputs are trimmed back by :func:`trim_pad`, and the
        loss side carries :meth:`place_mask` weights so the padding is
        weightless."""
        t0 = time.perf_counter_ns()
        return _note_placed(t0, self._place_batch(self.pad_to(tree, rows)))

    def place_mask(self, n_real: int, rows: int):
        """:meth:`row_mask` placed like any 1-D batch leaf."""
        return self.place_batch(self.row_mask(n_real, rows))

    # ---- steps ------------------------------------------------------------

    @property
    def state(self):
        return self._state

    @state.setter
    def state(self, value):
        # external assignment (checkpoint restore, re-formation): the
        # host step mirror is unknown until read, and so is which
        # stored program the new leaves fit
        self._state = value
        self._step_cache = None
        self._state_kind = None

    def train_step(self, features, labels, weights=None):
        # the timeline's ``enqueue``: mesh scope entry + the jitted call
        # returning (the device runs on); never a block
        t0 = time.perf_counter_ns()
        with self.mesh, attention_mesh_scope(self.mesh):
            step = self._stored(
                "train_step", self._train_step, (features, labels, weights)
            )
            self._state, metrics = step(
                self._state, features, labels, weights
            )
        TIMELINE.record_enqueue(t0, metrics)
        if self._step_cache is not None:
            self._step_cache += 1
        return metrics

    def train_steps_stacked(
        self, stacked_features, stacked_labels, stacked_weights=None
    ):
        """K optimizer steps in ONE dispatch: a jitted ``lax.scan`` over
        batches stacked on a leading axis (semantically identical to K
        sequential ``train_step`` calls).  Amortizes per-dispatch
        overhead.  Returns the last
        step's metrics.  ``stacked_weights``: optional ``(K, rows)``
        per-row sample weights (shape-canonical batching), scanned
        alongside the batches."""
        num_steps = jax.tree_util.tree_leaves(stacked_features)[0].shape[0]
        key = (num_steps, stacked_weights is not None)
        scan_fn = self._stacked_scan_cache.get(key)
        if scan_fn is None:
            step_fn = self._train_step
            weighted = stacked_weights is not None

            def scan_steps(state, feats, labels, weights=None):
                def body(s, xs):
                    s2, metrics = step_fn(
                        s, xs[0], xs[1], xs[2] if weighted else None
                    )
                    return s2, metrics

                xs = (feats, labels, weights) if weighted else (feats, labels)
                return jax.lax.scan(body, state, xs)

            # pin the updated state to the mesh layout exactly like
            # build_train_step does — without it the scan output's
            # sharding can drift from state_shardings and multi-process
            # host reads (checkpoint, dump) fail on the re-laid-out tree.
            # donate_batch extends donation to the stacked (k, rows, ...)
            # batch/weight inputs: dead after the scan, their memory is
            # reused for outputs (zero steady-state h2d allocations)
            scan_fn = jax.jit(
                scan_steps,
                donate_argnums=(0, 1, 2, 3)
                if self._donate_batch
                else (0,),
                out_shardings=(self.state_shardings, None),
            )
            self._stacked_scan_cache[key] = scan_fn
        batches = (stacked_features, stacked_labels)
        if stacked_weights is not None:
            batches += (stacked_weights,)
        t0 = time.perf_counter_ns()
        with self.mesh, attention_mesh_scope(self.mesh):
            scan_fn = self._stored("train_steps_stacked", scan_fn, batches)
            self._state, metrics = scan_fn(self._state, *batches)
        TIMELINE.record_enqueue(t0, metrics)
        if self._step_cache is not None:
            self._step_cache += int(num_steps)
        return jax.tree_util.tree_map(lambda m: m[-1], metrics)

    def _stored(self, name: str, jitted, batch: tuple):
        """``jitted`` itself, or, with the program store on, the stored
        program of that name for the state and ``batch`` as they are
        (shapes, dtypes, shardings): loaded, or built from ``jitted`` and
        stored.  Both take ``(state, *batch)`` and give ``(state,
        metrics)``."""
        if self._programs is None:
            if jitted not in self._jit_calls:
                # what train_programs() needs to ask jit for its executable
                self._jit_calls[jitted] = [
                    _shapes_of((self._state,) + batch), None
                ]
            return jitted
        if self._state_kind is None:
            self._state_kind = self._programs.kind_of(self._state)
        return self._programs.for_call(
            name, jitted, self._state, batch, self._state_kind
        )

    def train_programs(self) -> list:
        """The compiled train programs (``jax.stages.Compiled``) this
        trainer has dispatched: the program store's as it keeps them, a
        ``jit``-only trainer's asked of ``jit`` the way a call does (the
        lowering and the executable are the ones the calls made: nothing
        compiles).  For ``telemetry/op_scopes.py``; never on the train
        path."""
        if self._programs is not None:
            return self._programs.train_programs()
        with self.mesh, attention_mesh_scope(self.mesh):
            for jitted, call in self._jit_calls.items():
                if call[1] is None:
                    call[1] = jitted.lower(*call[0]).compile()
        return [compiled for _, compiled in self._jit_calls.values()]

    def place_stacked(self, tree):
        """Place a (K, batch, ...) stacked tree: same layout as
        :meth:`place_batch` per step with a replicated leading K axis."""
        from jax.sharding import PartitionSpec as P

        t0 = time.perf_counter_ns()

        def _place(x):
            x = np.asarray(x)
            per_step = self._batch_sharding(x.ndim - 1)
            sh = NamedSharding(
                self.mesh, P(None, *per_step.spec)
            )
            if not self._multiprocess:
                return jax.device_put(x, sh)
            return jax.make_array_from_callback(
                x.shape, sh, lambda idx: x[idx]
            )

        return _note_placed(t0, jax.tree_util.tree_map(_place, tree))

    def eval_step(self, features, labels, weights=None):
        with self.mesh, attention_mesh_scope(self.mesh):
            return self._eval_step(self.state, features, labels, weights)

    def predict_step(self, features):
        with self.mesh, attention_mesh_scope(self.mesh):
            return self._predict_step(self.state, features)

    @property
    def step(self) -> int:
        """Model version — served from a host mirror so per-batch version
        checks never force a device readback (a full sync +
        roundtrip); one readback re-seeds the mirror
        after any external state assignment."""
        if self._step_cache is None:
            self._step_cache = int(jax.device_get(self._state.step))
        return self._step_cache


class _StoredPrograms:
    """One trainer's programs through the program store: an identity
    for each (no trace needed), and the loaded or built program kept per
    call signature, as ``jit`` keeps its own."""

    def __init__(self, store, mesh: Mesh, identity: dict):
        self._store = store
        self._devices = list(mesh.devices.flat)
        # what every program of this trainer shares
        self.identity = identity
        self._kinds: dict = {}
        self._programs: dict = {}

    def noted_state_shapes(self, state_of):
        """The state's shapes rebuilt from the store's note of the model's
        variables (``state_of`` adds the optimizer's), else ``None``."""
        description = self._store.read_note(
            {**self.identity, "note": "variables"}
        )
        if description is None:
            return None
        return jax.eval_shape(
            state_of, program_store.shapes_from(description)
        )

    def note_state_shapes(self, state_shapes, state_of):
        """Keep the variables' paths, shapes and dtypes for the next
        process, if the state can be rebuilt from them as it is."""
        variables = {"params": state_shapes.params, **state_shapes.model_state}
        description = program_store.describe_shapes(variables)
        if description is None:
            return
        rebuilt = jax.eval_shape(
            state_of, program_store.shapes_from(description)
        )
        if jax.tree_util.tree_flatten(rebuilt) == jax.tree_util.tree_flatten(
            state_shapes
        ):
            self._store.write_note(
                {**self.identity, "note": "variables"}, description
            )

    def kind_of(self, tree) -> int | None:
        """A small number for the tree's structure with every leaf's
        shape, dtype and sharding (``None`` when a leaf is no device
        array: such a call stays on ``jit``)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        try:
            kind = (
                treedef,
                tuple((x.shape, x.dtype, x.sharding) for x in leaves),
            )
        except AttributeError:
            return None
        return self._kinds.setdefault(kind, len(self._kinds))

    def load_or_build(self, name, jitted, args, out_tree):
        """The program ``jitted`` is for ``args``, through the store."""
        identity = {
            **self.identity,
            "program": name,
            "arguments": program_store.describe_arrays(args),
        }
        return self._store.get_or_build(
            identity,
            lambda: jitted.lower(*args),
            jax.tree_util.tree_structure((args, {})),
            out_tree,
            self._devices,
        )

    def train_programs(self) -> list:
        return list(self._programs.values())

    def for_call(self, name, jitted, state, batch, state_kind):
        """A step's program for this state and batch, kept per call
        signature as ``jit`` keeps its own."""
        key = (name, state_kind, self.kind_of(batch))
        program = self._programs.get(key)
        if program is None:
            if None in key:
                return jitted
            program = self._programs[key] = self.load_or_build(
                name,
                jitted,
                (state,) + batch,
                # the updated state has the structure of the one handed
                # in (a scan could not carry it otherwise), the metrics
                # are build_train_step's
                jax.tree_util.tree_structure((state, {"loss": 0})),
            )
        return program


def _shapes_of(tree):
    """Every array of ``tree`` as the shape, dtype and sharding a lowering
    takes in its place."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        ),
        tree,
    )


def _note_placed(start_ns: int, placed):
    """One ``h2d_transfer`` span for a public ``place_*`` call, counting
    the bytes it placed; returns ``placed``."""
    nbytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(placed))
    TIMELINE.record(PHASE_H2D_TRANSFER, start_ns, count=nbytes)
    return placed


def _host_slice_for_init(sample_features):
    """A tiny host batch is enough to trace init (values are irrelevant)."""
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x)[:1], sample_features
    )


def trim_pad(outputs, n: int):
    """Drop the rows :meth:`SPMDTrainer.pad_to` added to reach the
    canonical shape (device arrays come back as host numpy)."""
    return jax.tree_util.tree_map(lambda x: np.asarray(x)[:n], outputs)
