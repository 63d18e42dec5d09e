"""The catch-up benchmark: ``python3 benchmark/run.py --workload <cell>``."""
