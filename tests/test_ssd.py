"""``ops/ssd.py``: the chunked state-space scan (kernels in interpret mode
and the plain chunk algebra) against the token-by-token recurrence, outputs
and every gradient."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures.ssm_hybrid_moe_reference import ssm_recurrence
from torchft_tpu.ops.ssd import KEPT_NAMES, ssd_chunked, ssd_chunked_plain

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
