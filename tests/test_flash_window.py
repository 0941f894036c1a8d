"""A sliding window in ``ops/flash_attention.py``: forward, ``dq`` and
``dkv`` against plain attention under an explicit mask, in interpret mode.
The cases moved out of ``tests/test_flash_attention.py`` (PR 50) so that
tier-1's workers share the kernels' tests; what the grids hold is still held
there (``test_window_walks_only_its_blocks_and_names_its_programs``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_flash_attention import _qkv, _ref_windowed
from torchft_tpu.ops.flash_attention import flash_attention

WINDOW_CASES = [
    # S, H, KV, D, bq, bk, window
    (512, 4, 2, 64, 128, 128, 256),  # a multiple of the block, under the sequence
    (512, 4, 2, 64, 128, 128, 200),  # no multiple of the block
    (512, 4, 2, 64, 128, 128, 1),  # the query's own position alone
    (512, 4, 2, 64, 128, 128, 129),  # one position past a block
    (512, 4, 2, 64, 128, 128, 512),  # at the sequence: causal
    (512, 4, 2, 64, 128, 128, 700),  # over the sequence: causal
    (512, 4, 2, 64, 64, 128, 150),  # row blocks smaller than key blocks
    (512, 4, 2, 64, 256, 64, 100),  # key blocks smaller than row blocks
    (256, 32, 4, 16, 64, 64, 96),  # 32 query heads over 4 KV heads
    # all three kinds of block in one launch (four row blocks and more, the
    # blocks unequal both ways), at GQA groups of 1, 8 and 16
    (256, 2, 2, 32, 64, 32, 128),  # a group of 1, a window of a whole number of blocks
    (256, 8, 1, 16, 64, 32, 100),  # a group of 8, a window that is no multiple of either block
    (256, 16, 1, 16, 32, 64, 128),  # a group of 16, key blocks the larger
    (256, 16, 1, 16, 32, 64, 100),
    (256, 8, 1, 16, 64, 32, 256),  # no window (at the sequence: causal), a group of 8
    (256, 16, 1, 16, 32, 64, 256),  # no window, a group of 16
]


@pytest.mark.parametrize("S,H,KV,D,bq,bk,window", WINDOW_CASES)
def test_window_forward_matches_masked_attention(S, H, KV, D, bq, bk, window) -> None:
    q, k, v = _qkv(1, S, H, KV, D)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, window=window, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_windowed(q, k, v, window)), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("wrt", ["dq", "dkv"])
@pytest.mark.parametrize("S,H,KV,D,bq,bk,window", WINDOW_CASES)
def test_window_backward_matches_masked_attention(S, H, KV, D, bq, bk, window, wrt) -> None:
    q, k, v = _qkv(1, S, H, KV, D)
    argnums = (0,) if wrt == "dq" else (1, 2)

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)))

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_q=bq, block_k=bk, window=window, interpret=True
    )
    got = jax.grad(loss(flash), argnums=argnums)(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _ref_windowed(q, k, v, window)), argnums=argnums)(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
