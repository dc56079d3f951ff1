"""The on-chip benchmark: `python3 benchmark/run.py --workload <cell> ...`.
See benchmark/README.md."""
