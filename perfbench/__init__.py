"""On-chip benchmark of hyperspace-tpu: ``python perfbench/run.py --help``."""
