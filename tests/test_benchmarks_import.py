"""Every driver kept in ``benchmarks/`` imports on the CPU backend.

None of them is a benchmark of record (``perf/run.py`` is); they are
builder's tools, and a tool no test touches is how one came never to
have run.  Importing runs no ``main``: no job, no port, no compile.
"""

import glob
import importlib.util
import os

import pytest

BENCHMARKS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"
)
DRIVERS = sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(BENCHMARKS, "*.py"))
)


def test_the_kept_drivers():
    assert DRIVERS == [
        "attention_sweep.py",
        "dispatch_overhead_bench.py",
        "expert_rows_sweep.py",
        "gated_delta_sweep.py",
        "ouro_loop_control.py",
        "ouro_loss_forms.py",
        "preemption_accuracy_bench.py",
        "reform_bench.py",
        "rope_sweep.py",
        "step_memory_aot.py",
    ]


@pytest.mark.parametrize("driver", DRIVERS)
def test_driver_imports(driver):
    spec = importlib.util.spec_from_file_location(
        f"_benchmarks_{driver[:-3]}", os.path.join(BENCHMARKS, driver)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.main)
    # each says at its top that it is no benchmark of record
    assert "ledger number" in " ".join(module.__doc__.split())
