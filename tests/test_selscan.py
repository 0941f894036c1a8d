"""``ops/selscan.py`` against the token-by-token recurrence: the kernels
(``selscan_fwd``, ``selscan_bwd``, in interpret mode) and the plain chunk walk,
forward and every operand's gradient, at decays from -1e-4 to -60 a token, over
several chunks and several channel blocks, in float32 and with bfloat16
operands.  The CPU.

Tolerances, with their reasons.  In float32 both sides run the SAME recurrence
in the same order of operations within a token; they differ in where a sum over
the channels or the states is associated (a block's lanes folded 128 on 128 and
then one product with ones, against one ``jnp.sum``).  That reads 2e-7 to 5e-7
of the largest entry: the limit is 2e-5 of the largest entry of what is
compared, forty times the reading.  A state or a decay held in bfloat16 reads
4e-3 to 2e-2 (the bfloat16 case below makes one on purpose and holds that it is
caught), so either fails the limit two hundred times over.  With bfloat16 ``u``,
``B`` and ``C`` (the dtype the model hands over) the recurrence itself is still
float32 and the output is ROUNDED to bfloat16 twice (the kernel's ``y``, which
a layer keeps, and ``y + D u``): 2^-7 of the largest entry."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import selscan

B, S, C, N, CHUNK = 2, 64, 256, 16, 16


def recurrence(u, dt, A, Bm, Cm, D, state_dtype=jnp.float32):
    """``y`` by the equations of the module's docstring, token by token."""
    u, dt, Bm, Cm = (a.astype(jnp.float32) for a in (u, dt, Bm, Cm))

    def token(h, now):
        u, dt, b, c = now
        h = (jnp.exp(dt[:, :, None] * A) * h + (dt * u)[:, :, None] * b[:, None, :]).astype(state_dtype)
        return h, jnp.sum(h.astype(jnp.float32) * c[:, None, :], axis=-1) + D * u

    start = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), state_dtype)
    _, y = jax.lax.scan(token, start, tuple(jnp.moveaxis(a, 1, 0) for a in (u, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


@functools.lru_cache(maxsize=None)
def operands(channels=C):
    """dt in [1e-4, 0.4] and A in [-150, -0.05]: exponents from -1e-4 (a state
    that barely decays over the whole sequence) to -60 a token (one that is
    gone after a token), both in one launch."""
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    u = jax.random.normal(k[0], (B, S, channels))
    dt = jnp.exp(jax.random.uniform(k[1], (B, S, channels), minval=np.log(1e-4), maxval=np.log(0.4)))
    A = -jnp.exp(jax.random.uniform(k[2], (channels, N), minval=np.log(0.05), maxval=np.log(150.0)))
    Bm, Cm = jax.random.normal(k[3], (B, S, N)), jax.random.normal(k[4], (B, S, N))
    D = jax.random.normal(k[5], (channels,))
    weight = jax.random.normal(k[6], (B, S, channels))  # of the output, in what a gradient is taken of
    return (u, dt, A, Bm, Cm, D), weight


WALKS = {
    "kernels": lambda *a: selscan.selscan(*a, chunk=CHUNK, interpret=True),
    "plain": lambda *a: selscan.selscan_plain(*a, chunk=CHUNK),
}


@functools.lru_cache(maxsize=None)
def both(walk, channels=C):
    """((y, gradients) by the recurrence, (y, gradients) by ``walk``), each ONE program."""
    args, weight = operands(channels)

    def side(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(lambda *a: jnp.sum(f(*a) * weight), argnums=range(6))(*a)))(*args)

    with jax.default_matmul_precision("highest"):
        return side(recurrence), side(WALKS[walk])


def _close(got, want, rel, name):
    np.testing.assert_allclose(got, want, atol=rel * float(jnp.max(jnp.abs(want))), rtol=0, err_msg=name)


def test_the_operands_reach_both_ends_of_the_decays():
    (u, dt, A, *_), _ = operands()
    exponent = dt[..., None] * A
    assert float(exponent.max()) > -1e-4 and float(exponent.min()) < -55.0


@pytest.mark.parametrize("walk", list(WALKS))
def test_the_output_is_the_recurrences(walk):
    (want, _), (got, _) = both(walk)
    _close(got, want, 2e-5, "y")


@pytest.mark.parametrize("operand", ["u", "dt", "A", "B", "C", "D"])
@pytest.mark.parametrize("walk", list(WALKS))
def test_every_operands_gradient_is_the_recurrences(walk, operand):
    (_, want), (_, got) = both(walk)
    at = "u dt A B C D".split().index(operand)
    assert float(jnp.max(jnp.abs(want[at]))) > 0
    _close(got[at], want[at], 2e-5, operand)


def test_several_channel_blocks_and_one_chunk_a_sequence():
    """1,024 channels are two blocks of 512 lanes: the sums over the channels
    (B's and C's gradients) are added over the blocks outside the kernel; a
    chunk as long as the sequence is one grid step a block."""
    assert selscan.channel_block(1024) == 512 and selscan.channel_block(5120) == 512 and selscan.channel_block(96) == 96
    (y, grads), (y_k, grads_k) = both("kernels", 1024)
    _close(y_k, y, 2e-5, "y")
    for name, g, w in zip("u dt A B C D".split(), grads_k, grads):
        _close(g, w, 2e-5, name)
    args, _ = operands()
    whole = jax.jit(lambda *a: selscan.selscan(*a, chunk=S, interpret=True))(*args)
    _close(whole, both("kernels")[0][0], 2e-5, "one chunk")


def test_a_state_held_in_bfloat16_fails_the_limit():
    """The limit is tight enough: the same recurrence with its state rounded
    to bfloat16 after every token is a hundred times outside it."""
    args, _ = operands()
    with jax.default_matmul_precision("highest"):
        coarse = jax.jit(functools.partial(recurrence, state_dtype=jnp.bfloat16))(*args)
    want = both("kernels")[0][0]
    assert float(jnp.max(jnp.abs(coarse - want))) > 100 * 2e-5 * float(jnp.max(jnp.abs(want)))


def test_bfloat16_operands_run_a_float32_recurrence():
    """What the model hands over: u, B and C in bfloat16, dt, A and D float32.
    The output is the float32 recurrence of the SAME rounded operands, rounded
    twice (the kernel's ``y`` and ``y + D u``)."""
    (u, dt, A, Bm, Cm, D), _ = operands()
    u, Bm, Cm = (a.astype(jnp.bfloat16) for a in (u, Bm, Cm))
    got = jax.jit(lambda *a: selscan.selscan(*a, chunk=CHUNK, interpret=True))(u, dt, A, Bm, Cm, D)
    assert got.dtype == jnp.bfloat16
    want = jax.jit(recurrence)(u, dt, A, Bm, Cm, D)
    _close(got.astype(jnp.float32), want, 2.0 ** -7, "y")


def test_a_sequence_that_the_chunk_does_not_divide_is_refused():
    (u, dt, A, Bm, Cm, D), _ = operands()
    with pytest.raises(ValueError, match="not divisible"):
        selscan.selscan(u[:, :60], dt[:, :60], A, Bm[:, :60], Cm[:, :60], D, chunk=CHUNK, interpret=True)


def test_a_rematerialised_caller_that_keeps_the_names_runs_the_forward_kernel_once():
    args, weight = operands()

    def layer(*a):
        return jnp.sum(selscan.selscan(*a, chunk=CHUNK, interpret=True) * weight)

    def launches(policy):
        text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(layer, policy=policy)))(*args))
        return text.count("name=selscan_fwd"), text.count("name=selscan_bwd")

    assert launches(jax.checkpoint_policies.save_only_these_names(*selscan.KEPT_NAMES)) == (1, 1)
    assert launches(jax.checkpoint_policies.nothing_saveable) == (2, 1)
