"""Chip benchmark of the registration engine: seconds per registered pair.

Entry point: ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Cells, configurations, traffic mixes,
correctness limits and per-layer metric readers are data files found by the
names in ``BENCHMARK.json`` (see ``chipbench/bench.py``).
"""
