"""The on-chip benchmark of EE-Join: ``python3 bench/run.py --workload <cell> ...``.

Data-driven: a cell of ``BENCHMARK.json`` names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<name>.json``) and
per-layer metrics (``metrics/<name>.py``), each found by its name.
"""
