"""The chip benchmark: ``python bench/run.py --workload <cell> ...``.

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
checkout's root; each is a file of its own under this directory, found by
that name (see ``harness.py``).
"""
