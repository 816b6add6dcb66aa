"""On-chip benchmark of the scan service: cells of a deployment under a
traffic mix, named in BENCHMARK.json at the repository root.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
