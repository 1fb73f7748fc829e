"""The one marker of the benchmark's tests."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "compiles_a_model: compiles a model, runs a reference or starts a "
        "process; test_perf_manifest.py's run of every tests/perf file over a "
        "grown copy of BENCHMARK.json leaves these out (a new test is in it "
        "unless it carries this mark)",
    )
