"""The chip benchmark of the one-hop sub-query result cache.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chips of the machine it starts
on. See ``bench/harness.py`` for what one run does.
"""
