"""ftbench: the on-chip benchmark of torchft_tpu (see README.md here)."""
