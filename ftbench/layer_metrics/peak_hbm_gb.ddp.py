"""``peak_hbm_gb`` in the cells with two replica groups, where the end-to-end
metric is ``ddp_tokens_per_s_per_chip``."""

from ftbench.sources import split_for

META, read = split_for("peak_hbm_gb", "ddp_tokens_per_s_per_chip")
