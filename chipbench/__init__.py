"""Chip benchmark of the queued serve path.

One command runs one cell (a model configuration under a traffic mix) on
the chips it is started on and prints one JSON result line:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own (``configs/<name>.json``,
``traffic/<name>.json``, ``metrics/<name>.py``), found by the name that
``BENCHMARK.json`` at the root of the checkout gives it.
"""
