"""The served-path benchmark of the CRDT store (BENCHMARK.json at the
repo root names its cells, configurations, traffic mixes and metrics).

Nothing here imports JAX at module level: the load-generator process
(benchmark/loadgen.py) imports the generators and must stay off the chip.
"""
