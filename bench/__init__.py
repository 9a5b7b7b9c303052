"""The chip benchmark: harness, configurations, traffic mixes, metric
readers, model families, peak table and plain reference. Each cell's
configuration, mix, per-layer readers and model family are files found by
name (``bench/configs/``, ``bench/traffic/``, ``bench/metrics/``,
``bench/arch/<arch>.py``; see ``bench/harness.py``).
``python3 bench/run.py --help``."""
