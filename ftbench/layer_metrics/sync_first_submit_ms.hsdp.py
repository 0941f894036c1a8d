"""``sync_first_submit_ms`` in the four-chip cell (two replica groups of two chips each): the
same reader under a name of its own, because tier-1's view of the benchmark
(``tests/test_ftbench_program_spans.py``) holds ``sync_first_submit_ms``'s list to one
cell and a ``benchmark`` PR may not edit it (README.md, "On four chips")."""

from ftbench.sources import split_for

META, read = split_for("sync_first_submit_ms", "ddp_tokens_per_s_per_chip")
