"""On-chip benchmark of the serving stack: one cell (configuration x traffic
mix) per run, everything found by the names in ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
