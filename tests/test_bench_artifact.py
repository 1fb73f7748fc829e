"""The bench artifact contract.

The driver records only a ~2000-char tail of bench.py's stdout, so the
LAST line must be a compact JSON summary that carries EVERY config's
headline numbers and gate verdicts in <= 1500 bytes, pointing at
``BENCH_full.json`` for detail — and the exit code must tell the truth:
0 only when the device answered and every config and phase ran.
"""

import importlib.util
import json
import os
import sys

import pytest

_BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", _BENCH_PATH)
    mod = importlib.util.module_from_spec(spec)
    # import only — main() is never called, so no jax/device work happens
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


def _fully_populated_models():
    """Every config the bench can emit, every optional field present —
    the worst case for compact-line size."""
    step = {
        "samples_per_sec_per_chip": 142857.3,
        "samples_per_sec_per_chip_median": 139000.1,
        "spread_pct": 31.4,
        "batch": 2048,
        "mfu": 0.2712,
        "model_tflops_per_sec_per_chip": 53.42,
        "vs_baseline": 1234.56,
    }
    tokens = dict(
        step, tokens_per_sec_per_chip=137000, vs_baseline=None
    )
    anatomy_overall = {
        "dispatches": 32,
        "e2e_vs_roofline": 0.912,
        "binding": "device_path",
        "phases": {"device_compute": {"p50_ms": 210.0, "p99_ms": 260.0}},
        "boundary_stall": {
            "boundaries": 3,
            "stall_ms": 412,
            "share_of_wall": 0.0312,
        },
    }
    e2e = {
        "e2e_samples_per_sec_per_chip": 234517.3,
        "batch": 4096,
        "records_measured": 1835008,
        "tasks_measured": 7,
        "vs_step_only": 0.211,
        # the instrumented anatomy windows: device prefetch on AND off
        "anatomy": {
            "prefetch_on": dict(anatomy_overall),
            "prefetch_off": dict(anatomy_overall, e2e_vs_roofline=0.695),
        },
        "budget": {
            "host_pipeline_records_per_sec": 1650000,
            "device_path_records_per_sec": 282000,
            "binding": "device_path",
            "e2e_vs_roofline": 0.831,
            "probe_dispatch_secs_e2e_start": 0.2468,
            "probe_dispatch_secs_before": 0.2471,
            "probe_dispatch_secs_after": 0.2513,
        },
    }
    return {
        "mnist": dict(step),
        "resnet50_cifar10": dict(step),
        "deepfm": dict(step),
        "imagenet_resnet50": dict(step),
        "transformer_seq8192": dict(tokens),
        "transformer_gpt2s_seq2048": dict(tokens),
        "mnist_e2e": dict(e2e),
        "deepfm_e2e": dict(e2e),
        "runtime_ratios": {
            "local_records_per_sec": 131072,
            "taskstream_records_per_sec": 120000,
            "taskstream_vs_local": 0.915,
            "lockstep_records_per_sec": 65000,
            "lockstep_e2e_vs_local": 0.496,
            "world_size": 2,
            "records": 131072,
            "batch": 512,
            "host_cores": 1,
        },
        "accuracy": {
            "mnist": {"accuracy": 0.9712, "steps": 937, "pass": True,
                      "threshold": 0.8},
            "census": {"accuracy": 0.818, "steps": 256, "pass": True,
                       "threshold": 0.8},
            "deepfm_frappe": {"accuracy": 0.9301, "steps": 256,
                              "pass": True, "threshold": 0.8},
        },
        "elastic_reform": {
            "reform_latency_secs": 0.38,
            "records_ok": True,
            "standby_activated": 2,
        },
        "accuracy_under_preemption": {
            "accuracy": 1.0,
            "records_ok": True,
            "pass": True,
            "reform_latency_secs": 0.38,
        },
    }


def test_compact_line_fits_the_driver_tail(bench):
    models = _fully_populated_models()
    compact = bench._compact_models(models)
    line = json.dumps(
        {
            "metric": "resnet50_cifar10_train_samples_per_sec_per_chip",
            "value": 142857.3,
            "unit": "samples/sec/chip",
            "vs_baseline": 1234.56,
            "device": "TPU v5 lite",
            "detail": "BENCH_full.json",
            "models": compact,
        },
        separators=(",", ":"),
    )
    # 1500 leaves ~500 chars of slack inside the driver's 2000-char tail
    # for stray stderr/warning lines sharing the capture
    assert len(line) <= 1500, f"{len(line)} bytes: {line}"
    # every config survives compaction with its headline number
    for name in models:
        assert name in compact
    assert compact["resnet50_cifar10"]["r"] == 142900  # 4 sig digits
    assert compact["resnet50_cifar10"]["mfu"] == 0.271
    assert compact["mnist_e2e"]["roof"] == 0.831
    assert compact["mnist_e2e"]["vs"] == 0.211
    assert compact["mnist_e2e"]["bind"] == "d"
    # measured anatomy ratios: prefetch ON is roofm, OFF is roofm0
    assert compact["mnist_e2e"]["roofm"] == 0.912
    assert compact["mnist_e2e"]["roofm0"] == 0.695
    # the between-task idle share rides in both windows' compact keys
    assert compact["mnist_e2e"]["bst"] == 0.0312
    assert compact["mnist_e2e"]["bst0"] == 0.0312
    assert compact["transformer_seq8192"]["tok"] == 137000
    assert compact["accuracy"]["mnist"] == [0.9712, 1]
    assert compact["elastic_reform"]["ok"] == 1
    assert compact["accuracy_under_preemption"]["ok"] == 1
    assert compact["runtime_ratios"] == {
        "ts_vs_local": 0.915,
        "lockstep_vs_local": 0.496,
    }


def test_compact_marks_failed_configs(bench):
    compact = bench._compact_models(
        {"mnist": {"error": "RESOURCE_EXHAUSTED mid-compile " * 8}}
    )
    assert compact["mnist"] == {"err": 1}
    # a failed accuracy SUB-config stays visible too (silent truncation
    # of gate failures is the r4 artifact bug class)
    compact = bench._compact_models(
        {
            "accuracy": {
                "mnist": {"error": "boom"},
                "census": {"accuracy": 0.81, "pass": True,
                           "threshold": 0.8},
            }
        }
    )
    assert compact["accuracy"]["mnist"] == {"err": 1}
    assert compact["accuracy"]["census"] == [0.81, 1]


def test_every_compact_key_is_in_the_legend(bench):
    compact = bench._compact_models(_fully_populated_models())
    for name, entry in compact.items():
        if name == "accuracy":
            continue  # values are [acc, pass] pairs keyed by config
        for key in entry:
            assert (
                key in bench.COMPACT_KEY_LEGEND
                or key == "lockstep_vs_local"
            ), f"{name}.{key} missing from COMPACT_KEY_LEGEND"


def test_failures_finds_every_error_marker(bench):
    """A config or phase that raised keeps its place in the artifact as
    an ``error`` marker; ``_failures`` names each one (nested phases
    included) so main() can exit non-zero."""
    # (a JSON round trip: the fixture's e2e entries share nested dicts)
    models = json.loads(json.dumps(_fully_populated_models()))
    assert bench._failures(models) == []
    models["mnist"] = {"error": "boom"}
    models["accuracy"]["census"] = {"error": "boom"}
    models["deepfm_e2e"]["anatomy"]["prefetch_on"] = {"error": "boom"}
    assert sorted(bench._failures(models)) == [
        "accuracy.census",
        "deepfm_e2e.anatomy.prefetch_on",
        "mnist",
    ]


def test_main_exits_nonzero_when_the_device_is_unreachable(
    bench, monkeypatch, tmp_path, capsys
):
    """No device: a stamped ``device_unreachable`` artifact, ``value:
    null`` on the last line, and exit code 1 — never 0."""
    # main() writes BENCH_full.json beside the module file
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    monkeypatch.setattr(
        bench,
        "_device_preflight",
        lambda: {"reason": "device init failed: no chip", "timeout_secs": 1},
    )
    assert bench.main() == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["value"] is None
    assert last["error"] == "device init failed: no chip"
    full = json.loads((tmp_path / "BENCH_full.json").read_text())
    assert full["device_unreachable"]["reason"] == last["error"]
    assert "stamped_at" in full["device_unreachable"]


def _stub_everything_but_one_step_config(bench, monkeypatch, tmp_path, measure):
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    monkeypatch.setattr(bench, "_device_preflight", lambda: None)
    monkeypatch.setattr(
        bench, "_configs", lambda n_chips=1: {"mnist": {"batch": 256}}
    )
    monkeypatch.setattr(bench, "_measure", measure)
    monkeypatch.setattr(bench, "E2E_CONFIGS", {})
    monkeypatch.setattr(
        bench,
        "_run_cpu_bench_script",
        lambda name: {"reform_latency_secs": 0.3, "records_ok": True},
    )
    monkeypatch.setattr(sys, "argv", ["bench.py", "--no-accuracy"])


def test_main_exits_nonzero_when_a_config_raises(
    bench, monkeypatch, tmp_path, capsys
):
    """One config raising must not take the others' numbers down — and
    must not be swallowed either: the artifact carries the marker, the
    exit code is 1."""

    def _raise(name, cfg, mesh):
        raise RuntimeError("kernel refused")

    _stub_everything_but_one_step_config(bench, monkeypatch, tmp_path, _raise)
    assert bench.main() == 1
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["models"]["mnist"] == {"err": 1}
    assert last["models"]["elastic_reform"]["ok"] == 1
    assert "failed: mnist" in captured.err
    full = json.loads((tmp_path / "BENCH_full.json").read_text())
    assert full["models"]["mnist"] == {"error": "kernel refused"}


def test_main_exits_zero_when_everything_ran(
    bench, monkeypatch, tmp_path, capsys
):
    _stub_everything_but_one_step_config(
        bench,
        monkeypatch,
        tmp_path,
        lambda name, cfg, mesh: {
            "samples_per_sec_per_chip": 1000.0,
            "batch": 256,
        },
    )
    assert bench.main() == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["models"]["mnist"]["r"] == 1000


def test_device_preflight_probes_once(bench, tmp_path):
    """One bounded probe in a subprocess: a hang or a failed init yields
    the structured payload main() exits 1 on; there is no second try."""
    import sys as _sys

    ok = bench._device_preflight(
        timeout_secs=30, probe_argv=[_sys.executable, "-c", "print('v5')"]
    )
    assert ok is None
    err = bench._device_preflight(
        timeout_secs=0.5,
        probe_argv=[_sys.executable, "-c", "import time; time.sleep(30)"],
    )
    assert "did not answer" in err["reason"]
    assert err["timeout_secs"] == 0.5
    # a failed init propagates the stderr tail — after exactly ONE run
    counter = tmp_path / "runs"
    probe = (
        "import sys\n"
        f"open({str(counter)!r}, 'a').write('x')\n"
        "sys.stderr.write('no chip found'); sys.exit(3)\n"
    )
    err = bench._device_preflight(
        timeout_secs=30, probe_argv=[_sys.executable, "-c", probe]
    )
    assert "no chip found" in err["reason"]
    assert counter.read_text() == "x"


def test_retry_machinery_stays_gone(bench):
    """The degraded-window retry, its 'typical' rates (read from a
    BENCH_full.json that was never committed), the preflight back-off
    and their env knobs are deleted: a measurement is taken once and
    reported as measured."""
    for name in (
        "_retry_if_degraded",
        "_typical_rates",
        "_e2e_typical",
        "TYPICAL_RATE",
        "TYPICAL_E2E_RATE",
    ):
        assert not hasattr(bench, name), name
    src = open(_BENCH_PATH).read()
    for gone in (
        "degraded",
        "TYPICAL_RATE",
        "EDL_BENCH_PREFLIGHT",
        "backoff",
    ):
        assert gone not in src, gone
    assert "deg" not in bench.COMPACT_KEY_LEGEND
