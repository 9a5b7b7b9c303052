"""The chip benchmark: harness, configurations, traffic mixes, metric
readers, peak table and plain reference. ``python3 bench/run.py --help``."""
