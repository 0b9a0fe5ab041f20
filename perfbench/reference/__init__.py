"""The plain reference: NumPy and Python only, nothing of ``sentinel_tpu``."""
