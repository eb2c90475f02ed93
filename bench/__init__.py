"""The on-chip benchmark: archive snapshots through `compress_pytree`.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json`. Configurations (`configs/`), traffic
mixes (`traffic/`) and per-layer metric readers (`metrics/`) are files
found by the names `BENCHMARK.json` gives them.
"""
