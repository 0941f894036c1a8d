"""``ops/ssd.py``: the chunked state-space scan (kernels in interpret mode
and the plain chunk algebra) against the token-by-token recurrence, outputs
and every gradient; ONE group of 16 and of 64 heads in head blocks; a group of
one block against the program it was before there were blocks."""

import functools
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures.ssm_hybrid_moe_reference import ssm_recurrence
from torchft_tpu.ops.ssd import HEAD_BLOCK, KEPT_NAMES, head_block, ssd_chunked, ssd_chunked_plain

from tests._once import once_a_run

B, S, H, G, P, N, CHUNK = 2, 64, 4, 2, 8, 16, 16
NAMES = ("x", "dt", "A_log", "B", "C", "D")


def recurrence(*operands):
    """The plain reference's token-by-token recurrence (``S_t = a_t S_{t-1} +
    dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``), float32 at full precision."""
    with jax.default_matmul_precision("highest"):
        return ssm_recurrence(*operands)


def operands(seed, decay):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    Bm = jax.random.normal(ks[1], (B, S, G, N), jnp.float32) / np.sqrt(N)
    Cm = jax.random.normal(ks[2], (B, S, G, N), jnp.float32)
    D = 1.0 + 0.1 * jax.random.normal(ks[3], (H,), jnp.float32)
    A_log = jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0))
    # log-uniform steps: "slow" decays a token by at most 1.6e-3 (a near 1),
    # "fast" by up to exp(-80) (a near 0), "mixed" spans both inside a chunk
    lo, hi = dict(slow=(1e-5, 1e-4), fast=(0.5, 5.0), mixed=(1e-4, 5.0))[decay]
    dt = jnp.exp(jax.random.uniform(ks[5], (B, S, H), jnp.float32, np.log(lo), np.log(hi)))
    return x, dt, A_log, Bm, Cm, D


@functools.lru_cache(maxsize=None)
def run(path, grad=False):
    """The scan on ``path`` (``recurrence``: the reference's), or the
    gradients of its output summed under a weight, as ONE program that the
    three decays share: their operands differ in values alone, and run
    operation by operation each case compiled its own few hundred."""
    f = {
        "kernels": lambda *a: ssd_chunked(*a, chunk=CHUNK, interpret=True),
        "plain": lambda *a: ssd_chunked_plain(*a, chunk=CHUNK),
        "recurrence": recurrence,
    }[path]
    if grad:
        return jax.jit(jax.grad(lambda weight, *a: jnp.sum(f(*a) * weight), argnums=range(1, 7)))
    return jax.jit(f)


@pytest.mark.parametrize("decay", ["slow", "fast", "mixed"])
@pytest.mark.parametrize("path", ["kernels", "plain"])
def test_outputs_match_the_recurrence(path, decay):
    args = operands(1, decay)
    want = run("recurrence")(*args)
    got = run(path)(*args)
    assert got.shape == (B, S, H, P) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("decay", ["slow", "fast", "mixed"])
@pytest.mark.parametrize("path", ["kernels", "plain"])
def test_every_gradient_matches_the_recurrence(path, decay):
    args = operands(2, decay)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P), jnp.float32)
    want = run("recurrence", grad=True)(weight, *args)
    got = run(path, grad=True)(weight, *args)
    for name, g, w in zip(NAMES, got, want):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(g / scale, w / scale, rtol=1e-4, atol=2e-5, err_msg=f"d{name}")


def test_one_chunk_and_short_sequences():
    """A sequence shorter than the chunk is one chunk of its own length."""
    args = tuple(a[:, :8] if a.ndim > 1 else a for a in operands(3, "mixed"))
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (args[3], args[4]))
    got = ssd_chunked(*args, chunk=128, interpret=True)
    # the first token reads its own write alone
    x, dt = args[0], args[1]
    first = dt[:, 0, :, None] * x[:, 0] * jnp.sum(Bh[:, 0] * Ch[:, 0], axis=-1)[..., None] + args[5][:, None] * x[:, 0]
    np.testing.assert_allclose(got[:, 0], first, rtol=1e-5, atol=1e-5)


def test_kept_names_spare_a_rematerialised_layer_the_forward_kernel():
    """With ``KEPT_NAMES`` in the policy the gradient's program holds
    ``ssd_fwd`` once; with nothing kept, twice."""
    args = operands(4, "mixed")

    def count(policy):
        f = jax.checkpoint(lambda *a: ssd_chunked(*a, chunk=CHUNK, interpret=True), policy=policy)
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=range(6)))(*args))
        return text.count("name=ssd_fwd"), text.count("name=ssd_bwd")

    assert count(jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)) == (1, 1)
    assert count(jax.checkpoint_policies.nothing_saveable) == (2, 1)


def test_refuses_what_does_not_divide():
    x, dt, A_log, Bm, Cm, D = operands(5, "mixed")
    with pytest.raises(ValueError, match="not divisible"):
        ssd_chunked(x[:, :40], dt[:, :40], A_log, Bm[:, :40], Cm[:, :40], D, chunk=16, interpret=True)


# ---------------------------------------------------------------------------
# a group wider than a kernel's block (PR 69: granite-4.0-h-micro's ONE group of 64 heads)
# ---------------------------------------------------------------------------

WIDE_S, WIDE_P, WIDE_N = 512, 8, 16  # four chunks of 128, two of 256
ONE_BLOCK_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "ssd_one_block.json")


def wide_operands(heads, groups=1, seed=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (1, WIDE_S, heads, WIDE_P), jnp.float32)
    Bm = jax.random.normal(ks[1], (1, WIDE_S, groups, WIDE_N), jnp.float32) / np.sqrt(WIDE_N)
    Cm = jax.random.normal(ks[2], (1, WIDE_S, groups, WIDE_N), jnp.float32)
    D = 1.0 + 0.1 * jax.random.normal(ks[3], (heads,), jnp.float32)
    A_log = jnp.log(jax.random.uniform(ks[4], (heads,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(ks[5], (1, WIDE_S, heads), jnp.float32, np.log(1e-4), np.log(0.5)))
    return x, dt, A_log, Bm, Cm, D


@functools.lru_cache(maxsize=None)
def wide_side(path, heads, chunk):
    """(the output, its six cotangents under one weight) of ONE group of
    ``heads`` heads on ``path``, as one program; the recurrence's side is made
    once a run of the tests."""
    args = wide_operands(heads)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape, jnp.float32)
    f = {
        "kernels": lambda *a: ssd_chunked(*a, chunk=chunk, interpret=True),
        "plain": lambda *a: ssd_chunked_plain(*a, chunk=chunk),
        "recurrence": recurrence,
    }[path]
    run = lambda: jax.jit(jax.value_and_grad(lambda *a: (lambda y: (jnp.sum(y * weight), y))(f(*a)), argnums=range(6), has_aux=True))(*args)  # noqa: E731
    (_, y), grads = once_a_run(f"ssd-wide-recurrence-{heads}", run) if path == "recurrence" else run()
    return y, grads


def _assert_side_agrees(got, want, rtol=1e-4):
    """Outputs to 1e-4 and every cotangent to 1e-4 of the leaf's largest: a chunk of 256 sums 256
    float32 terms where the recurrence carries one state (3 of 262,144 outputs read 5e-5 off), and
    ``A_log``'s cotangent is ONE number a head summed over 512 tokens of products of such running
    sums (3e-5 of its largest), in float32's order of additions alone."""
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    for name, g, w in zip(NAMES, got[1], want[1]):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(g / scale, w / scale, rtol=rtol, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("chunk", [128, 256])
@pytest.mark.parametrize("heads", [16, 64])
def test_one_group_of_many_heads_in_head_blocks_matches_the_recurrence(heads, chunk):
    """ONE group of 16 and of 64 heads, two and eight blocks of ``HEAD_BLOCK``
    under one chunk of ``B`` and ``C``: the output and all six cotangents, of
    which ``dB``, ``dC`` and ``d(C B^T)`` are sums over the blocks made in the
    kernel's scratch, against the token recurrence."""
    assert head_block(heads) == HEAD_BLOCK == 8 and heads // HEAD_BLOCK in (2, 8)
    _assert_side_agrees(wide_side("kernels", heads, chunk), wide_side("recurrence", heads, chunk))


@pytest.mark.parametrize("heads,chunk", [(16, 128), (16, 256), (64, 256)])
def test_one_group_of_many_heads_matches_the_plain_chunk_algebra(heads, chunk):
    """The same against ``ssd_chunked_plain``, whose sums over a group's heads
    are jax's: the kernels' block sums differ from it in the order of float32
    additions alone.  (64 heads at ONE of the two chunks: the plain path
    unrolls a group's heads and its gradient at 64 compiles for half a minute.)"""
    _assert_side_agrees(wide_side("kernels", heads, chunk), wide_side("plain", heads, chunk), rtol=2e-5)


def test_a_block_is_any_multiple_of_eight_that_divides_the_group():
    args = wide_operands(16)
    whole = jax.jit(lambda *a: ssd_chunked(*a, chunk=256, block=16, interpret=True))(*args)  # the group in ONE block
    np.testing.assert_allclose(wide_side("kernels", 16, 256)[0], whole, rtol=1e-5, atol=1e-5)
    assert head_block(8) == 8 and head_block(4) == 4 and head_block(64, 32) == 32
    assert head_block(12) == 12 and head_block(20) == 20  # a group that eights do not divide is one block, as it was
    for heads, block in ((64, 12), (64, 24), (20, 8)):
        with pytest.raises(ValueError, match="does not divide into blocks"):
            head_block(heads, block)


def _one_block_digest():
    """The hash of the text that the gradient of 8 groups of 8 heads lowers to
    (kernels interpreted), jax's counters on private functions' names out."""
    args = wide_operands(64, groups=8)
    grad = jax.grad(lambda *a: jnp.sum(ssd_chunked(*a, chunk=128, interpret=True)), argnums=range(6))
    jax.clear_caches()
    text = re.sub(r"@([A-Za-z_]\w*?)_\d+\b", r"@\1", jax.jit(grad).lower(*args).as_text())
    return hashlib.sha256(text.encode()).hexdigest()


def test_eight_groups_of_eight_run_the_program_they_ran_before_there_were_blocks():
    """A group of ONE block (Nemotron's 8 groups of 8 heads) lowers to the
    bytes it lowered to on PR 69's PARENT (``python tests/test_ssd.py --write``
    on that commit wrote the fixture): the same program, so the parent's bits."""
    with open(ONE_BLOCK_FIXTURE) as f:
        want = json.load(f)
    if want["jax"] != jax.__version__:
        pytest.skip(f"the digest was written under jax {want['jax']}")
    assert _one_block_digest() == want["sha256"]


if __name__ == "__main__" and "--write" in sys.argv:
    with open(ONE_BLOCK_FIXTURE, "w") as f:
        json.dump({"jax": jax.__version__, "sha256": _one_block_digest()}, f, indent=1)
