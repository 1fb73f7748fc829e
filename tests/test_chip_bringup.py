"""What the chip bring-up changed, pinned on the CPU (ISSUE 21).

Everything here is a count, a path or a decision — never a speed: the
compile-cache directory every process resolves, the per-worker chip
binding as a pure function of world coordinates, which platforms run
the pallas kernels interpreted, ``chip_smoke.py``'s refusal to report
without a chip, content-keyed staleness of the native codec, and the
compiled four-chip step's view of the attention kernel (AOT, from
libtpu's topology description — no chip involved).
"""

import functools
import json
import os
import shutil
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- one compile cache, placeable from outside -------------------------------


def test_cache_dir_env_wins_and_code_sets_none(monkeypatch):
    from elasticdl_tpu.parallel import elastic

    monkeypatch.setenv(elastic.COMPILATION_CACHE_ENV, "/somewhere/else")
    # JAX's own reading of the environment stands: the code sets NO dir,
    # flag or no flag
    assert elastic.resolve_compilation_cache_dir("") is None
    assert elastic.resolve_compilation_cache_dir("/from/flag") is None


def test_cache_dir_flag_then_fixed_in_checkout_default(monkeypatch):
    from elasticdl_tpu.parallel import elastic

    monkeypatch.delenv(elastic.COMPILATION_CACHE_ENV, raising=False)
    assert elastic.resolve_compilation_cache_dir("/from/flag") == "/from/flag"
    default = elastic.resolve_compilation_cache_dir("")
    assert default == os.path.join(REPO, ".jax_compilation_cache")
    # git-ignored: a run must not dirty the checkout
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compilation_cache/" in f.read().split()


def test_cache_dir_is_the_same_from_two_processes_and_two_cwds(tmp_path):
    """The directory is part of what a cache hit depends on: resolved
    from the package location, never from the cwd, a pid or the clock."""
    probe = (
        "from elasticdl_tpu.parallel import elastic; "
        "elastic.configure_compilation_cache(); "
        "import jax; print(jax.config.jax_compilation_cache_dir)"
    )
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    seen = {
        subprocess.run(
            [sys.executable, "-c", probe],
            cwd=cwd,
            env=env,
            capture_output=True,
            text=True,
            check=True,
        ).stdout.strip()
        for cwd in (REPO, str(tmp_path))
    }
    assert seen == {os.path.join(REPO, ".jax_compilation_cache")}


def test_configure_compilation_cache_leaves_the_env_choice_alone(monkeypatch):
    import jax

    from elasticdl_tpu.parallel import elastic

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(elastic.COMPILATION_CACHE_ENV, "/somewhere/else")
    try:
        elastic.configure_compilation_cache("/from/flag")
        assert jax.config.jax_compilation_cache_dir == before
        # the two thresholds are set either way: cache every executable
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---- one process per chip -----------------------------------------------------


def test_chip_binding_is_a_pure_function_of_world_coordinates():
    from elasticdl_tpu.parallel.elastic import chip_binding_env

    world = [chip_binding_env(i, 4) for i in range(4)]
    assert world == [chip_binding_env(i, 4) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in world] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in world] == ["0", "1", "2", "3"]
    ports = [e["TPU_PROCESS_PORT"] for e in world]
    assert len(set(ports)) == 4
    for i, env in enumerate(world):
        # one chip per process on a 2x2 host, every peer's address known
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
        addresses = env["TPU_PROCESS_ADDRESSES"].split(",")
        assert addresses == [f"localhost:{p}" for p in ports]
        assert addresses[i].endswith(env["TPU_PROCESS_PORT"])
    assert chip_binding_env(0, 1)["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert chip_binding_env(1, 2)["TPU_PROCESS_BOUNDS"] == "2,1,1"
    assert chip_binding_env(7, 8)["TPU_PROCESS_BOUNDS"] == "2,4,1"
    # no grid for 3: a line, which the chip may refuse (ROADMAP A6/B5)
    assert chip_binding_env(2, 3)["TPU_PROCESS_BOUNDS"] == "3,1,1"
    with pytest.raises(ValueError):
        chip_binding_env(4, 4)


def test_local_manager_binds_cold_spawns_and_standbys(monkeypatch):
    """A cold spawn gets its binding in the environment; a standby was
    spawned before its world existed, so the binding rides its
    assignment line (worker/main.py applies it before any backend)."""
    from elasticdl_tpu.master import master as master_mod
    from elasticdl_tpu.parallel.elastic import chip_binding_env

    spawned = {}

    class _Popen:
        def __init__(self, argv, env=None, stdin=None):
            spawned["env"] = env
            self.pid = 1

    monkeypatch.setattr(master_mod.subprocess, "Popen", _Popen)
    im = master_mod.LocalInstanceManager.__new__(
        master_mod.LocalInstanceManager
    )
    im._master = type("M", (), {"port": 1})()
    im._envs = {}
    im._build_argv = lambda worker_id, addr, **world: ["mod"]
    world = dict(
        coordinator_addr="localhost:1",
        num_processes=4,
        process_id=2,
        cluster_version=0,
    )
    im._spawn(5, **world)
    for key, value in chip_binding_env(2, 4).items():
        assert spawned["env"][key] == value
    # a standby has no coordinates yet: nothing to bind at spawn
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    im._spawn(0, stdin_pipe=True, standby=1)
    assert "TPU_VISIBLE_CHIPS" not in spawned["env"]

    class _Standby:
        pid = 2
        written = b""

        def poll(self):
            return None

        def write(self, data):
            self.written += data

        def flush(self):
            pass

    standby = _Standby()
    standby.stdin = standby
    im._lock = threading.Lock()
    im._procs = {}
    im._standbys = [standby]
    im.standby_activations = 0
    assert im._activate_standby(9, world)
    assignment = json.loads(standby.written)
    assert assignment["env"] == chip_binding_env(2, 4)
    assert assignment["worker_id"] == 9 and assignment["process_id"] == 2


def test_master_side_memory_sample_never_starts_a_backend():
    """The master's ledger samples at reform edges; reading device
    memory there must not be the call that initializes a backend (on a
    chip it would take every chip of the host from the workers)."""
    probe = (
        "from elasticdl_tpu.telemetry import memory\n"
        "from jax._src import xla_bridge\n"
        "assert memory.read_device_memory() == {}\n"
        "memory.install().sample('reform_start')\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run(
        [sys.executable, "-c", probe],
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
        check=True,
    )


# ---- no fallback that hides the device ------------------------------------------


def test_kernel_interpret_selection():
    from elasticdl_tpu.ops.attention import kernel_interpret

    assert kernel_interpret("cpu") is True
    assert kernel_interpret("tpu") is False
    for other in ("gpu", "cuda", "some_plugin", ""):
        with pytest.raises(ValueError):
            kernel_interpret(other)


def test_mesh_log_names_platform_kind_and_count(caplog):
    import logging

    from elasticdl_tpu.parallel.mesh import MeshConfig

    logger = logging.getLogger("elasticdl_tpu")
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger="elasticdl_tpu"):
            MeshConfig.from_string("dp=2").create()
    finally:
        logger.removeHandler(caplog.handler)
    assert "over 2 cpu devices (cpu)" in caplog.text


def test_local_result_names_the_device(tmp_path):
    """A CPU run exits 0 just like a chip run: the result the CLI logs
    must say where it ran."""
    from elasticdl_tpu import client
    from elasticdl_tpu.data.recordio_gen import synthetic

    data = synthetic.gen_mnist(
        str(tmp_path / "d"), num_records=16, num_shards=1, seed=0
    )
    result = client.run(
        [
            "train",
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data",
            data,
            "--minibatch_size",
            "8",
            "--mesh_shape",
            "dp=1",
        ]
    )
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert result["steps"] == 2


# ---- chip_smoke.py without a chip -------------------------------------------------


def _run_smoke(script, cwd):
    return subprocess.run(
        [sys.executable, script],
        cwd=cwd,
        env=dict(os.environ),  # JAX_PLATFORMS=cpu here: the script pins tpu
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_chip_smoke_fails_fast_without_a_chip():
    proc = _run_smoke(os.path.join(REPO, "chip_smoke.py"), REPO)
    assert proc.returncode == 3
    # no result line: stdout stays empty, one clear line on stderr
    assert proc.stdout == ""
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("chip_smoke: JAX found no 'tpu' device")


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "not beside this script" in proc.stderr


def test_chip_smoke_parent_never_imports_jax():
    probe = (
        "import sys, chip_smoke\n"
        "assert chip_smoke.main(['--runs', 'nope']) == 1\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
    )
    subprocess.run(
        [sys.executable, "-c", probe], cwd=REPO, check=True,
        capture_output=True,
    )
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        head = f.read().split("# ---- children")[0]
    assert "import jax" not in head


# ---- the native codec is current by content ----------------------------------------


def test_native_codec_staleness_is_decided_by_content(tmp_path, monkeypatch):
    from elasticdl_tpu.data import recordio
    from elasticdl_tpu.data.recordio import build as build_mod

    if shutil.which("g++") is None:
        pytest.skip("g++ unavailable")
    source = tmp_path / "_native.cc"
    shutil.copy(build_mod.SOURCE, source)
    monkeypatch.setattr(build_mod, "SOURCE", str(source))
    monkeypatch.setattr(build_mod, "OUTPUT", str(tmp_path / "_native.so"))
    monkeypatch.setattr(recordio, "_lib", None)

    assert not build_mod.is_current()
    assert not recordio.native_available()
    # a checkout with no library: the entry points' call builds it
    assert recordio.ensure_native_codec() == build_mod.OUTPUT
    assert build_mod.is_current()

    # a copy does not preserve mtimes: an OLDER library of the SAME
    # source is still current...
    os.utime(build_mod.OUTPUT, (1, 1))
    assert build_mod.is_current()
    # ...and a NEWER library of OTHER source is not
    source.write_text(source.read_text() + "\n// changed\n")
    os.utime(build_mod.OUTPUT, None)
    assert not build_mod.is_current()
    monkeypatch.setattr(recordio, "_lib", None)
    assert not recordio.native_available()  # the stale one is not loaded
    with open(build_mod.OUTPUT, "rb") as f:
        stale = f.read()
    recordio.ensure_native_codec()  # rebuilds from the changed source
    assert build_mod.is_current()
    with open(build_mod.OUTPUT, "rb") as f:
        assert f.read() != stale
    monkeypatch.setattr(recordio, "_lib", None)  # drop the tmp library


def test_native_codec_unbuildable_fails_loudly(tmp_path, monkeypatch):
    from elasticdl_tpu.data import recordio
    from elasticdl_tpu.data.recordio import build as build_mod

    source = tmp_path / "_native.cc"
    source.write_text("this is not C++\n")
    monkeypatch.setattr(build_mod, "SOURCE", str(source))
    monkeypatch.setattr(build_mod, "OUTPUT", str(tmp_path / "_native.so"))
    monkeypatch.setattr(recordio, "_lib", None)
    with pytest.raises(RuntimeError, match="missing and unbuildable"):
        recordio.ensure_native_codec()
    assert os.listdir(tmp_path) == ["_native.cc"]  # no half-built leftovers


# ---- the compiled four-chip step (AOT, no chip) ---------------------------------------


@functools.lru_cache(maxsize=None)
def _compiled_for_v5e(script: str, *args: str) -> dict:
    """Run an AOT script of this directory in a process of its own (it
    describes the topology at its top level) and return the JSON line it
    prints; skip where libtpu gives no topology description."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", script), *args],
        env=dict(
            os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled"
        ),
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode == 77:
        pytest.skip(f"no TPU topology description here: {proc.stderr[-200:]}")
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_step_maps_the_kernel_over_the_per_chip_batch():
    """Compiled (not interpreted), a pallas kernel is an opaque custom
    call GSPMD cannot partition — JAX refuses to lower it bare inside a
    multi-device program.  ``ops.attention.attention`` maps it over the
    mesh's batch axes, so the COMPILED dp=4 train step hands each chip's
    kernel its own quarter of the batch.  Compiled here for a v5e 2x2
    from libtpu's topology description: no chip, no speed."""
    seen = _compiled_for_v5e("aot_four_chip_step.py")
    # global batch 8 over dp=4, 2 heads: each call sees 2*2 folded rows
    assert seen["device_kind"] == "TPU v5 lite"
    assert seen["kernel_calls"] >= 3  # forward, dQ, dK/dV
    assert seen["kernel_batch_x_heads"] == [4]
    assert seen["tokens_param"] == "s32[2,256]"


def test_gpt2_small_step_compiles_to_no_loop():
    """The benchmark's GPT-2-small step (8 x 1,024 tokens, 50,257-wide
    head), compiled for a v5e from libtpu's topology description, holds no
    ``while``: with a loss that gathered by label, the per-row ``vmap`` of
    ``weighted_mean_loss`` compiled to a scatter into a flat
    f32[411,705,344] buffer and two layout-copy loops around it (44 ms of
    a 109 ms step on the chip, PERF.md PR 25).  No chip, no speed."""
    seen = _compiled_for_v5e("aot_gpt2_small_step.py")
    assert seen["device_kind"] == "TPU v5 lite"
    assert seen["kernel_calls"] == 36  # 12 layers x (forward, dQ, dK/dV)
    assert seen["while_loops"] == 0
    assert seen["dynamic_update_slices"] == 0
    # the temporaries the flat buffer and its copies held: 7.95 GB with them
    assert seen["temp_bytes"] < 5e9


def test_gpt2_small_step_copies_no_operand_of_the_flash_kernels():
    """The same compiled step: the kernels read heads out of the
    projections' own (batch, tokens, heads * 64) rows and the projections
    are 2-D products (``layers/attention.py::HeadsDense``), so XLA puts no
    copy or transpose of a q-sized bf16 array on either side of a kernel.
    With folded heads it held 96 (eight a layer, 1.7 ms of a 60 ms step on
    the chip; with the kernels alone in lanes and ``nn.DenseGeneral``'s 4-D
    bias add, 98: PERF.md section 6, PR 36).  No chip, no speed."""
    seen = _compiled_for_v5e("aot_gpt2_small_step.py")
    assert seen["activation_copies"] == 0
    # the folded copies were temporaries: 3.33 GB with them
    assert seen["temp_bytes"] < 3e9
    # mapped over dp=4 the per-device kernels are handed the same rows: with
    # 4-D arrays at the mapped region's boundary the step held 156
    assert (
        _compiled_for_v5e("aot_gpt2_small_step.py", "--chips", "4")[
            "activation_copies"
        ]
        == 0
    )
