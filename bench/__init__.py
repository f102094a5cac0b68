"""The benchmark of the graph query service: cells named in
``BENCHMARK.json`` at the repository root, run one at a time by
``python3 bench/run.py``.

Everything that belongs to one configuration, traffic mix, graph
generator or metric lives in a file of its own, found by name:

    bench/configs/<config>.json    deployment: graph, sizes, service settings
    bench/traffic/<traffic>.json   clients per kernel, deadline, roots
    bench/graphs/<graph>.py        generate(config, seed) -> BenchGraph
    bench/metrics/<metric>.py      read(run) -> float | None

The rest of the package is the yardstick shared by every cell: the
closed-loop client (``harness``), the host reference and the comparison
that decides ``correct`` (``reference``, ``check``), the lower-precision
control (``control``), the profiler-trace reduction (``trace``) and the
table of device peaks (``peaks.json``).
"""
