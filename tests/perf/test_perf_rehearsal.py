"""The command, end to end, on the CPU backend at a tiny size: the cell is
one that only these tests add (files under tests/perf plus manifest
entries).  A rehearsal says the control flow is right and what the program
counts; it writes no device metric."""

import json
import os
import subprocess
import sys

import pytest
from perf_testlib import (
    ROOT,
    TINY_CELL,
    TINY_FAMILY_CELL,
    TINY_RESIDENT_CELL,
    manifest_with_tiny_cell,
)


def rehearse(tmp_path, trace: int, seed: int, cell: str = TINY_CELL, extra=()):
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(manifest_with_tiny_cell()))
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--manifest", str(manifest),
            "--rehearse-cpu", *extra,
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


@pytest.mark.parametrize(
    "trace,seed,cell",
    [
        (0, 7, TINY_CELL),
        (1, 2**31 + 11, TINY_CELL),
        (1, 9, TINY_RESIDENT_CELL),
        (0, 2**31 + 3, TINY_FAMILY_CELL),
    ],
)
@pytest.mark.compiles_a_model
def test_rehearsal_on_cpu(tmp_path, trace, seed, cell):
    info, result = rehearse(tmp_path, trace, seed, cell)
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    # no time, rate or share of a CPU run is written under a metric's name
    assert result["metrics"] == {}
    assert "busy_s" not in result["device"]
    assert result["correct"] is True, info["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert info["compiles_in_window"] == 0
    assert info["last_loss"] < info["first_loss"]
    assert info["readings"] >= 5
    assert info["q1"] <= info["median"] <= info["q3"]
    # the path holds the dispatcher's completed records to its own count;
    # traffic mode resident has no dispatcher and claims no such check
    if cell == TINY_RESIDENT_CELL:
        assert "records_completed" not in info["checks"]
    else:
        assert info["checks"]["records_completed"]
        assert info["checks"]["none_failed"]
    # the rate is the window's total, with the readings' median beside it
    assert info["total_over_window"] == info["units"] / info["window_s"]
    # ``setup_s`` is the set-up the repo's code does, the four parts after
    # the imports and the runtime's start; those, and the whole figure from
    # the process's first line, stay on the info line beside it (PR 64)
    parts = info["setup_parts"]
    assert list(parts) == ["imports_s", "data_s", "build_s", "warmup_s", "fill_s"]
    assert all(seconds >= 0 for seconds in parts.values())
    assert abs(info["setup_s"] - (sum(parts.values()) - parts["imports_s"])) < 1e-3
    assert abs(info["since_process_start_s"] - sum(parts.values())) < 1e-3
    assert 0 < info["setup_s"] < info["since_process_start_s"]
    if trace:
        assert info["untraced_rate"] > 0 and info["traced_rate"] > 0
        # the comparison with the plain reference the configuration names
        # (tests/perf/references/plain_lm.py) joins `correct`
        compared = info["reference"]
        assert info["checks"]["reference_agrees"] is compared["agrees"] is True
        assert compared["loss_err"] <= compared["tolerance"]["loss"]
        assert compared["grad_err"] <= compared["tolerance"]["grad"]
        assert set(compared["by_block"]) >= {"tok_embed", "block_0", "lm_head"}
        assert compared["sample"] == {"records": 4, "seed": seed}
        assert compared["seconds"] > 0
    else:
        assert info["window_s"] >= 2.0
        assert info["reference"] == "traced run only"
        assert "reference_agrees" not in info["checks"]


def test_without_the_program_the_command_fails_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths is not a checkout of the system under test."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    # the paths of the file that is copied, whatever manifest these tests read
    with open(tmp_path / "BENCHMARK.json") as f:
        paths = json.load(f)["paths"]
    for path in paths:
        shutil.copytree(
            os.path.join(ROOT, path), tmp_path / path,
            ignore=shutil.ignore_patterns("__pycache__", ".data"),
        )
    done = subprocess.run(
        [
            sys.executable, str(tmp_path / "perf" / "run.py"),
            "--workload", "gpt2s_seq1024", "--seed", "1", "--seconds", "1",
            "--trace", "0",
        ],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert done.returncode == 2
    assert done.stdout == ""
    assert "not beside the benchmark" in done.stderr


BROKEN = '''
import sys
sys.path.insert(0, {root!r})
from elasticdl_tpu.models import long_seq_transformer as zoo
sound = zoo.loss
# an answer altered where it is produced: the loss reads the logits halved,
# in the step the window times and in the comparison's system side alike
zoo.loss = lambda labels, outputs: sound(labels, outputs * 0.5)
import perf.run
sys.exit(perf.run.main())
'''


@pytest.mark.compiles_a_model
def test_a_run_whose_model_is_broken_underneath_is_not_correct(tmp_path):
    """The whole of a traced run but the look for a chip, with the model's
    answer altered under the harness: the job still trains (its loss falls,
    nothing compiles in the window, every record completes), so only the
    comparison with the plain reference can say so, and ``correct`` comes
    out false with each compared number beside its limit."""
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(manifest_with_tiny_cell()))
    script = tmp_path / "broken.py"
    script.write_text(BROKEN.format(root=ROOT))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, str(script), "--workload", TINY_CELL, "--seed",
            str(2**31 + 41), "--seconds", "2", "--trace", "1",
            "--manifest", str(manifest), "--rehearse-cpu",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    info, result = map(json.loads, done.stdout.strip().splitlines()[-2:])
    assert result["correct"] is False
    failed = [name for name, ok in info["checks"].items() if not ok]
    assert failed == ["reference_agrees"], info["checks"]
    assert info["last_loss"] < info["first_loss"]
    # the numbers compared, last in the result line and last on stderr
    assert list(result)[-1] == "compared"
    numbers = result["compared"]
    assert set(numbers) == {"compiles_in_window", "last_loss", "loss_err", "grad_err"}
    assert numbers["loss_err"]["value"] > numbers["loss_err"]["limit"]
    assert numbers["grad_err"]["value"] > numbers["grad_err"]["limit"]
    said = [l for l in done.stderr.splitlines() if l.startswith("perf/run.py: compared ")]
    assert [l.split()[2] for l in said] == list(numbers)
    assert done.stderr.strip().splitlines()[-1] == said[-1]


@pytest.mark.compiles_a_model
def test_the_control_through_the_harness_is_not_correct(tmp_path):
    """``--control``: a whole traced rehearsal whose comparison puts the plain
    reference, its weights rounded through ``float8_e4m3fn``, in the
    program's place.  The job is sound (every other check holds), and the
    gradient's limit alone says not correct, through ``reference.compare``
    itself; the same command at a cell's own size is the control on the
    chip."""
    info, result = rehearse(tmp_path, 1, 2**31 + 43, extra=("--control",))
    assert result["correct"] is False
    assert [name for name, ok in info["checks"].items() if not ok] == ["reference_agrees"]
    assert info["reference"]["in_the_programs_place"] == "the reference with float8_weights"
    grad = result["compared"]["grad_err"]
    assert grad["value"] > grad["limit"], result["compared"]
