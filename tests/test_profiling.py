"""XLA profiler window: --profile_dir captures a step-window trace
(TensorBoard 'profile' plugin artifacts) during local training."""

import glob
import os

from elasticdl_tpu.data.recordio_gen import synthetic
from elasticdl_tpu.trainer.local_executor import LocalExecutor
from elasticdl_tpu.utils.args import parse_master_args
from elasticdl_tpu.utils.profiling import StepProfiler


def test_local_training_writes_profile(tmp_path):
    train = synthetic.gen_mnist(
        str(tmp_path / "t"), num_records=192, num_shards=1, seed=0
    )
    profile_dir = str(tmp_path / "prof")
    args = parse_master_args(
        [
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data",
            train,
            "--minibatch_size",
            "32",
            "--records_per_task",
            "96",
            "--profile_dir",
            profile_dir,
            "--profile_steps",
            "2",
        ]
    )
    LocalExecutor(args).run()
    traces = glob.glob(
        os.path.join(profile_dir, "**", "*.trace.json*"), recursive=True
    ) + glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    assert traces, f"no trace artifacts under {profile_dir}"


def test_step_profiler_inactive_without_dir():
    prof = StepProfiler("", num_steps=3)
    for step in range(10):
        prof.on_step(step)  # must be a no-op, not a crash
    prof.stop()


def test_step_profiler_window_bounds(monkeypatch, tmp_path):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d, **_options: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    out = str(tmp_path / "p")
    prof = StepProfiler(out, start_step=2, num_steps=3)
    for step in range(10):
        prof.on_step(step)
    prof.stop()  # idempotent after the window closed
    assert calls == [("start", out), ("stop",)]


def test_a_run_without_a_window_never_reads_the_steps_bytes(tmp_path, monkeypatch):
    """The byte side is read on demand (a window's close, a benchmark's
    reader): ``LocalExecutor.run`` with no profile window calls none of it,
    nor the text of a program."""
    from elasticdl_tpu.telemetry import memory, op_scopes

    called = []
    for module, name in (
        (memory, "read_step_memory"), (memory, "dump_step_memory"),
        (memory, "device_bytes"), (op_scopes, "live_bytes"),
        (op_scopes, "xla_sizes"), (op_scopes, "scope_map"),
        (op_scopes, "_live_of_text"), (op_scopes, "watched_programs"),
    ):
        monkeypatch.setattr(
            module, name,
            lambda *args, _name=name, **kwargs: called.append(_name),
        )
    train = synthetic.gen_mnist(
        str(tmp_path / "t"), num_records=192, num_shards=1, seed=0
    )
    args = parse_master_args(
        [
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data", train,
            "--minibatch_size", "32",
            "--records_per_task", "96",
        ]
    )
    executor = LocalExecutor(args)
    executor.run()
    assert executor._version == 6  # it trained
    assert called == []
