"""The program store: a relaunched process LOADS its step instead of
deriving it again.

JAX's persistent compile cache is keyed by the lowered module, so a warm
process still traces the whole model to a jaxpr and lowers the jaxpr to
MLIR only to hash the result, find the cache entry and load it (6-9 s of
Python in every benchmark cell, 26 s of the one recovery ever timed).  The
store sits AHEAD of trace and lower: it maps an identity that is computed
without tracing to the compiled program, serialised with
``jax.experimental.serialize_executable``.  On a hit the trainer
deserialises and calls the stored executable; on a miss, or on ANY doubt
about an entry (short file, foreign header, an executable the runtime
will not load), it lowers and compiles exactly as before and then writes
the entry.

Where it lives: ``program_store/`` under the persistent compile cache's
directory, switched on in the one place the compile cache is
(:func:`elasticdl_tpu.parallel.elastic.configure_compilation_cache`).  A
process that never calls that builds its programs with plain ``jit``.

The identity is sound before it is fast (docs/designs/program_store.md
lists what it covers and what invalidates an entry).  Each entry also
records the SHA-256 of the lowered module that produced it, so a test
(never the train path) can re-trace and prove that a hit would have
produced the same module.  Deleting the directory is always safe.

A program that goes to the store is kept OUT of the compile cache: its
bytes are on disk once.
"""

from __future__ import annotations

import hashlib
import importlib.metadata
import json
import os
import struct
import tempfile
import time
import zlib

import jax

from elasticdl_tpu.telemetry import compile_tracker
from elasticdl_tpu.utils.log_utils import default_logger as logger

try:
    import zstandard
except ImportError:  # the compile cache falls back to zlib the same way
    zstandard = None

FORMAT = 1
DIRECTORY_NAME = "program_store"
_MAGIC = b"EDLPROG\x01"
_SUFFIX = ".program"
_NOTE_SUFFIX = ".note.json"

# Parsed job arguments that reach NO traced function: none of them is
# read by ``build_model``, ``build_optimizer``, ``loss`` or
# ``device_parse``, nor by the trainer's step builders
# (tests/test_program_store.py holds the traced side of the package and
# the model zoo to that).  Everything else the parser knows is part of a
# program's identity: an argument added later is covered until someone
# proves it out.
ARGUMENTS_OUTSIDE_EVERY_PROGRAM = frozenset(
    {
        # a relaunched worker's coordinates: the mesh and the device ids
        # carry what a program sees of them
        "worker_id",
        "process_id",
        "num_processes",
        "slice_id",
        "standby",
        "cluster_version",
        "coordinator_addr",
        "master_addr",
        # the host side of the data plane: which records, in which order
        "shuffle_seed",
        "training_data",
        "validation_data",
        "prediction_data",
        "num_epochs",
        "records_per_task",
        "num_minibatches_per_task",
        # where a run leaves its files, and how much it says
        "job_name",
        "log_level",
        "telemetry_dir",
        "trace_sample_rate",
        "profile_dir",
        "profile_steps",
        "tensorboard_log_dir",
        "checkpoint_dir",
        "checkpoint_dir_for_init",
        "checkpoint_steps",
        "keep_checkpoint_max",
        "output",
        "compilation_cache_dir",
        "metrics_host",
        "metrics_port",
        "port",
    }
)

_active: "ProgramStore | None" = None


def enable(directory: str) -> "ProgramStore":
    """Switch the store on for this process
    (``configure_compilation_cache`` is the one caller)."""
    global _active
    if _active is None or _active.directory != directory:
        _active = ProgramStore(directory)
    return _active


def disable():
    global _active
    _active = None


def active() -> "ProgramStore | None":
    return _active


# ---- identity ---------------------------------------------------------------


def job_identity(args, model_module) -> dict:
    """What of a job is a constant of its programs: every parsed argument
    but :data:`ARGUMENTS_OUTSIDE_EVERY_PROGRAM` (model parameters and the
    learning rate are baked into a program; they appear in no argument of
    it), and the directory of the model zoo module the job names."""
    return {
        "arguments": {
            name: value
            for name, value in sorted(vars(args).items())
            if name not in ARGUMENTS_OUTSIDE_EVERY_PROGRAM
        },
        "model_zoo_directory": os.path.dirname(
            os.path.abspath(model_module.__file__)
        ),
    }


def source_digest(directory: str) -> str:
    """SHA-256 over every ``.py`` under ``directory``: relative path and
    content, in sorted order."""
    digest = hashlib.sha256()
    for root, subdirs, files in os.walk(directory):
        subdirs.sort()
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            digest.update(os.path.relpath(path, directory).encode())
            digest.update(b"\0")
            with open(path, "rb") as f:
                digest.update(f.read())
            digest.update(b"\0")
    return digest.hexdigest()


def versions() -> dict:
    """The installed versions a compiled program depends on."""
    found = {}
    for package in ("jax", "jaxlib", "libtpu", "flax", "optax", "numpy"):
        try:
            found[package] = importlib.metadata.version(package)
        except importlib.metadata.PackageNotFoundError:
            found[package] = None
    return found


_PACKAGE_DIRECTORY = os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)


def process_identity(job: dict, mesh) -> dict:
    """The part of an identity every program of one trainer shares: the
    code, the installation, the devices and the job."""
    devices = list(mesh.devices.flat)
    client = devices[0].client
    return {
        "format": FORMAT,
        "sources": {
            "elasticdl_tpu": source_digest(_PACKAGE_DIRECTORY),
            "model_zoo": source_digest(job["model_zoo_directory"]),
        },
        "versions": versions(),
        "backend": {
            "platform": client.platform,
            # names the runtime's own build (libtpu's, on a TPU)
            "platform_version": client.platform_version,
            "device_kind": devices[0].device_kind,
            "process_index": client.process_index(),
            "process_count": len({d.process_index for d in client.devices()}),
        },
        "mesh": {
            "axis_names": list(mesh.axis_names),
            "shape": list(mesh.devices.shape),
            "device_ids": [d.id for d in devices],
            "device_processes": [d.process_index for d in devices],
        },
        "job": job["arguments"],
        "environment": {
            name: os.environ.get(name)
            for name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
        },
        # every jax config value: the ones that change tracing or
        # lowering are among them, and the rest are stable per deployment
        "jax_config": dict(sorted(jax.config.values.items())),
    }


def describe_sharding(sharding) -> list:
    spec = getattr(sharding, "spec", None)
    return [
        type(sharding).__name__,
        str(spec) if spec is not None else repr(sharding),
        sharding.memory_kind,
    ]


def describe_arrays(tree) -> list | None:
    """``[path, shape, dtype, weak type, sharding]`` of every leaf, or
    ``None`` when a leaf is no ``jax.Array`` (no identity: the caller
    stays on plain ``jit``)."""
    described = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not isinstance(leaf, jax.Array):
            return None
        described.append(
            [
                jax.tree_util.keystr(path),
                list(leaf.shape),
                str(leaf.dtype),
                bool(leaf.aval.weak_type),
                describe_sharding(leaf.sharding),
            ]
        )
    return described


def describe_shapes(tree) -> list | None:
    """``[keys, shape, dtype]`` of every leaf of a tree of nested
    string-keyed dicts (flax variables), from which :func:`shapes_from`
    rebuilds the tree; ``None`` for any other container."""
    described = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(entry, "key", None) for entry in path]
        if not all(
            isinstance(entry, jax.tree_util.DictKey) and isinstance(key, str)
            for entry, key in zip(path, keys)
        ):
            return None
        described.append([keys, list(leaf.shape), str(leaf.dtype)])
    return described


def shapes_from(description: list) -> dict:
    """The nested dicts of ``jax.ShapeDtypeStruct`` a
    :func:`describe_shapes` list names."""
    tree: dict = {}
    for keys, shape, dtype in description:
        node = tree
        for key in keys[:-1]:
            node = node.setdefault(key, {})
        node[keys[-1]] = jax.ShapeDtypeStruct(tuple(shape), dtype)
    return tree


def canonical(identity: dict) -> bytes:
    return json.dumps(
        identity, sort_keys=True, separators=(",", ":"), default=repr
    ).encode()


def module_digest(lowered) -> str:
    """SHA-256 of a lowered program's MLIR text."""
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


# ---- the store ---------------------------------------------------------------


class EntryRefused(Exception):
    """An entry was found and cannot be used; the reason is the message."""


def _compress(payload: bytes) -> tuple[str, bytes]:
    if zstandard is not None:
        return "zstd", zstandard.ZstdCompressor(write_checksum=True).compress(
            payload
        )
    return "zlib", zlib.compress(payload)


def _decompress(compression: str, packed: bytes) -> bytes:
    if compression == "zstd" and zstandard is not None:
        return zstandard.ZstdDecompressor().decompress(packed)
    if compression == "zlib":
        return zlib.decompress(packed)
    raise ValueError(f"compression {compression!r} cannot be read here")


def _compile_outside_the_compile_cache(lowered):
    """Compile ``lowered`` (served by the persistent compile cache if it
    holds the program) without writing it there: the store keeps the
    executable, and its bytes should be on disk once.  The threshold is
    the process's: a program another thread compiles meanwhile is not
    cached either, and compiles again in the next process."""
    name = "jax_persistent_cache_min_compile_time_secs"
    previous = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        return lowered.compile()
    finally:
        jax.config.update(name, previous)


class ProgramStore:
    """``identity -> compiled program`` on disk, one file an entry."""

    def __init__(self, directory: str):
        self.directory = directory
        self._refusals_logged: set[str] = set()
        # the store counts there, and asks it what the compile cache served
        compile_tracker.install()

    def path(self, identity: dict, suffix: str = _SUFFIX) -> str:
        name = hashlib.sha256(canonical(identity)).hexdigest()
        return os.path.join(self.directory, name + suffix)

    def get_or_build(self, identity, lower, in_tree, out_tree, devices):
        """The compiled program ``identity`` names: loaded from its entry,
        else built by ``lower().compile()`` and written.

        ``in_tree`` / ``out_tree``: the call's pytree structures, which the
        caller computes without tracing (they hold the model's ``apply``
        and the optimizer, so they are never pickled).  ``devices``: the
        mesh's, in its order."""
        path = self.path(identity)
        try:
            compiled = self._load(path, identity, in_tree, out_tree, devices)
        except EntryRefused as refusal:
            compile_tracker.record_program_store_reject()
            self._log_refusal(path, str(refusal))
        else:
            if compiled is not None:
                return compiled
            compile_tracker.record_program_store_miss()
        lowered = lower()
        served = compile_tracker.compile_cache_hits()
        compiled = _compile_outside_the_compile_cache(lowered)
        if compiled.out_tree != out_tree or compiled.in_tree != in_tree:
            # a program whose structure the caller cannot foresee cannot
            # be loaded without a trace: it stays a jit-only program
            self._log_refusal(path, "the built program's pytrees differ")
            return compiled
        if (
            devices[0].platform == "cpu"
            and compile_tracker.compile_cache_hits() > served
        ):
            # XLA:CPU cannot serialise an executable it LOADED: what it
            # writes has lost its kernels, the next process's load
            # succeeds and its first dispatch fails ("Function ... not
            # found").  A program the compile cache served stays where it
            # is there, and is traced as before until that entry goes.
            # (The TPU runtime writes a loaded executable whole: PERF.md,
            # PR 31.)
            return compiled
        try:
            self._write(path, identity, lowered, compiled)
        except (OSError, ValueError, NotImplementedError) as ex:
            logger.warning("program store: %s not written: %s", path, ex)
        return compiled

    def read_header(self, path: str) -> dict:
        with open(path, "rb") as f:
            return self._read_header(f)

    def _read_header(self, f) -> dict:
        fixed = f.read(len(_MAGIC) + 8)
        if len(fixed) != len(_MAGIC) + 8 or not fixed.startswith(_MAGIC):
            raise EntryRefused("no program store entry (magic)")
        (header_bytes,) = struct.unpack(">Q", fixed[len(_MAGIC) :])
        raw = f.read(header_bytes)
        if len(raw) != header_bytes:
            raise EntryRefused("short file (header)")
        try:
            return json.loads(raw)
        except ValueError as ex:
            raise EntryRefused(f"header is no JSON: {ex}") from ex

    def _load(self, path, identity, in_tree, out_tree, devices):
        from jax.experimental import serialize_executable

        start = time.monotonic()
        try:
            f = open(path, "rb")
        except FileNotFoundError:
            return None
        with f:
            header = self._read_header(f)
            if header.get("format") != FORMAT:
                raise EntryRefused(f"format {header.get('format')!r}")
            # the file's name is a hash of the identity; the header holds
            # the identity itself, so a foreign or colliding entry is seen
            if canonical(header.get("identity")) != canonical(identity):
                raise EntryRefused("header names another identity")
            packed = f.read()
        if len(packed) != header.get("payload_bytes"):
            raise EntryRefused(
                f"short file ({len(packed)} of "
                f"{header.get('payload_bytes')} payload bytes)"
            )
        try:
            payload = _decompress(header.get("compression"), packed)
            compiled = serialize_executable.deserialize_and_load(
                payload,
                in_tree,
                out_tree,
                backend=devices[0].client,
                execution_devices=devices,
            )
        except Exception as ex:  # noqa: BLE001 — any doubt is a refusal
            raise EntryRefused(
                f"executable not loaded: {type(ex).__name__}: {ex}"
            ) from ex
        compile_tracker.record_program_load(time.monotonic() - start)
        return compiled

    def _write(self, path, identity, lowered, compiled):
        from jax.experimental import serialize_executable

        payload, _, _ = serialize_executable.serialize(compiled)
        compression, packed = _compress(payload)
        header = canonical(
            {
                "format": FORMAT,
                "identity": identity,
                "module_sha256": module_digest(lowered),
                "compression": compression,
                "payload_bytes": len(packed),
            }
        )
        self._write_whole(
            path, _MAGIC, struct.pack(">Q", len(header)), header, packed
        )

    def _write_whole(self, path: str, *pieces: bytes):
        """Two workers of one host may race: each writes its own
        temporary file, and the rename puts a whole file in place or
        none."""
        os.makedirs(self.directory, exist_ok=True)
        fd, temporary = tempfile.mkstemp(
            dir=self.directory, prefix=".writing-"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                for piece in pieces:
                    f.write(piece)
            os.replace(temporary, path)
        except BaseException:
            try:
                os.unlink(temporary)
            except FileNotFoundError:
                pass
            raise

    # ---- notes: small facts a trainer needs BEFORE it can name a program

    def read_note(self, identity: dict):
        """What :meth:`write_note` kept under ``identity``, else ``None``
        (a note that cannot be read is no note)."""
        try:
            with open(self.path(identity, _NOTE_SUFFIX), "rb") as f:
                kept = json.loads(f.read())
        except (OSError, ValueError):
            return None
        if not isinstance(kept, dict) or canonical(
            kept.get("identity")
        ) != canonical(identity):
            return None
        return kept.get("note")

    def write_note(self, identity: dict, note):
        path = self.path(identity, _NOTE_SUFFIX)
        try:
            self._write_whole(
                path, canonical({"identity": identity, "note": note})
            )
        except OSError as ex:
            logger.warning("program store: %s not written: %s", path, ex)

    def _log_refusal(self, path: str, reason: str):
        if reason in self._refusals_logged:
            return
        self._refusals_logged.add(reason)
        logger.warning(
            "program store: %s refused (%s); building the program instead",
            path,
            reason,
        )
