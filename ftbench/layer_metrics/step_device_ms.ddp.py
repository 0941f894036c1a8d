"""``step_device_ms`` in the cells with two replica groups, where the end-to-end
metric is ``ddp_tokens_per_s_per_chip``."""

from ftbench.sources import split_for

META, read = split_for("step_device_ms", "ddp_tokens_per_s_per_chip")
