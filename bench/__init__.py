"""On-chip benchmark of the discord-search system (see BENCHMARK.json).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once, on the chip, and prints one JSON
result as the last line of standard output.  Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of
its own under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``,
found by the name that ``BENCHMARK.json`` gives it.
"""
