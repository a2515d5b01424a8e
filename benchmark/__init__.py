# Benchmark data — the analog of reference python/benchmark/ (§2.13):
# seeded synthetic data generation (`gen_data.py`, `gen_data_distributed.py`).
# The chip benchmark itself is `chipbench/`.
