"""On-chip serving benchmark: one cell (model configuration x traffic mix)
per run, driven through the program's normal serving path.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it: ``bench/configs/<config>.json`` (whose ``"arch"`` names
``bench/arch/<arch>.py``), ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``.
"""
