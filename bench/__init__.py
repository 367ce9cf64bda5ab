"""Chip benchmark of the serving path: one cell is one model configuration
under one traffic mix.

Run from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is data found by name: ``BENCHMARK.json`` lists the
cells and metrics, ``bench/configs/<config>.json`` holds a model
configuration as it is run, ``bench/traffic/<traffic>.json`` a traffic mix,
``bench/workloads/<cell>.json`` the serving settings and load of one cell,
and ``bench/metrics/<metric>.py`` the reader of one metric.
"""
