"""On-chip benchmark of the transfer simulator.

Entry point: ``python -m bench.run --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` from the repository root.  Everything a cell needs is
found by name from ``BENCHMARK.json``: ``configs/<config>.json``,
``traffic/<mix>.json`` and one reader per per-layer metric in
``layers/<metric>.py``.
"""
