"""Chip benchmark: cells named in BENCHMARK.json, run one per process by
``benchmarks/chip/run.py``. Configurations, traffic mixes and per-layer
metric readers are files found by name (configs/, traffic/, metrics/)."""
