"""The benchmark of ``BENCHMARK.json``: harness, traffic generator, FLOP
functions, peaks, trace reduction and per-layer readers.  ``perf/run.py``
is the command; everything else is found by the names in the manifest."""
