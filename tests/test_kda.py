"""``ops/kda.py``: the chunked gated delta rule (Pallas, interpret mode
here) against the per-token recurrence of the plain reference, forward and
gradients.

Float32 on the CPU, unit-length q and k, values of order one, ``g`` in
[-5, 0].  Tolerances: the two forms add the same terms in another order
(the chunked one through a 32- or 64-wide triangular solve and decay
factors up to exp(80) that cancel), so they differ by float32's rounding
times a few hundred terms: 2e-5 absolute on outputs and gradients of order
one (read here: at most 7e-6).  A lower precision anywhere (bfloat16
operands) reads 1e-2 and fails.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures.ling_hybrid_reference import kda_recurrence
from torchft_tpu.ops import kda

TOL = 2e-5


def _inputs(seed, B=2, S=128, H=2, dk=32, dv=32, decay="drawn"):
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(r.standard_normal((B, S, H, dk)))
    k = unit(r.standard_normal((B, S, H, dk)))
    v = r.standard_normal((B, S, H, dv))
    g = -5.0 / (1.0 + np.exp(-2.0 * r.standard_normal((B, S, H, dk))))
    if decay == "bound":
        # the gate's lower bound on every channel, with a token now and then
        # that forgets nothing: both ends of exp's range inside one sub-block
        g = np.full_like(g, -5.0)
        g[:, ::3] = -1e-3
    beta = 1.0 / (1.0 + np.exp(-r.standard_normal((B, S, H))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


SCALE = 32 ** -0.5  # of ``_inputs``' keys


@functools.lru_cache(maxsize=None)
def run(form, chunk=None, grad=False):
    """The rule in one ``form`` (the reference's ``recurrence``, the
    ``kernels`` in interpret mode, the ``plain`` chunk algebra), or the
    gradients of its output summed under a weight, as ONE program at full
    precision that the cases of a chunk share: run operation by operation
    each case compiled its own few hundred."""
    f = {
        "recurrence": lambda *a: kda_recurrence(*a, SCALE),
        "kernels": lambda *a: kda.kda_chunked(*a, chunk=chunk, interpret=True),
        "plain": lambda *a: kda.kda_chunked_plain(*a, chunk=chunk),
    }[form]
    program = jax.jit(jax.grad(lambda weight, *a: jnp.sum(f(*a) * weight), argnums=range(1, 6)) if grad else f)

    def at_full_precision(*a):
        with jax.default_matmul_precision("highest"):
            return program(*a)

    return at_full_precision


@pytest.mark.parametrize("decay", ["drawn", "bound"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_kernels_agree_with_the_recurrence(chunk, decay):
    args = _inputs(11, decay=decay)
    assert args[0].shape[-1] ** -0.5 == SCALE
    weight = jnp.asarray(np.random.default_rng(5).standard_normal(args[2].shape), jnp.float32)
    want, want_grads = run("recurrence")(*args), run("recurrence", grad=True)(weight, *args)
    got, got_grads = run("kernels", chunk)(*args), run("kernels", chunk, grad=True)(weight, *args)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    for name, a, b in zip("qkvgb", got_grads, want_grads):
        assert float(jnp.max(jnp.abs(a - b))) < TOL, name


@pytest.mark.parametrize("chunk", [32, 64])
def test_plain_chunk_algebra_is_the_kernels(chunk):
    """What a model takes off the TPU: the same chunk function under a
    scan, differentiated by jax, so it also checks the hand-written
    backward against jax's own."""
    args = _inputs(12)
    weight = jnp.asarray(np.random.default_rng(6).standard_normal(args[2].shape), jnp.float32)
    assert float(jnp.max(jnp.abs(run("kernels", chunk)(*args) - run("plain", chunk)(*args)))) < 1e-6
    for a, b in zip(run("kernels", chunk, grad=True)(weight, *args), run("plain", chunk, grad=True)(weight, *args)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_bfloat16_operands_stay_near_the_recurrence():
    """The chip's types: bfloat16 q, k, v with the state, the decays and
    the inverse in float32.  8e-3 of the output's largest value is
    bfloat16's own rounding (2^-8) through a few products; the state in
    bfloat16 would read ten times that."""
    args = _inputs(13)
    want = run("recurrence")(*args)
    q, k, v, g, beta = args
    got = kda.kda_chunked(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta,
        chunk=64, interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 8e-3 * float(jnp.max(jnp.abs(want))) + 4e-3


def test_shapes_that_do_not_divide_are_refused():
    q, k, v, g, beta = _inputs(14, S=96)
    with pytest.raises(ValueError):
        kda.kda_chunked(q, k, v, g, beta, chunk=64, interpret=True)
