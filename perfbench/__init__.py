"""The benchmark of sentinel-tpu: harness, yardstick and plain reference.

Everything the driver runs lives here and in ``BENCHMARK.json``.  From the
program it takes only the system under test (``SentinelClient``), its spans
and its kernel names.
"""
