"""``ops/gdn.py``: the chunked gated delta rule with ONE decay a head (Pallas,
interpret mode here) against the per-token recurrence of the plain reference,
forward and gradients, at decays drawn down to -60 a token, at two value heads
a key head and at one, over several chunks.

Float32 on the CPU, unit-length q and k, values of order one.  Tolerances:
the two forms add the same terms in another order (the chunked one through a
32- or 64-wide triangular solve); a decay across several tokens is ``exp`` of
the sum of THOSE tokens' ``g`` alone in both (a difference of two running sums
near -2,000, sixty tokens of -30, would carry their rounding: 1e-4 of a decay
that matters, which an earlier form of the kernels read).  Outputs of up to
0.2 within 1e-6 (read here: 5e-8 at most), a gradient within 5e-6 of values of
up to ten (read: 5e-7 at most).  A lower precision anywhere (bfloat16
operands) reads 1e-3 on the outputs and fails.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ftbench.architectures.gated_delta_moe_reference import delta_recurrence
from torchft_tpu.ops import gdn, kda

OUT_TOL, GRAD_TOL = 1e-6, 5e-6
DK = 32
SCALE = DK ** -0.5
LOW = -60.0  # the most negative log decay a token is drawn at


def _inputs(seed, r, B=2, S=128, Hk=2, dk=DK, dv=32, low=LOW):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((B, S, Hk, dk)))
    k = unit(rng.standard_normal((B, S, Hk, dk)))
    v = rng.standard_normal((B, S, Hk * r, dv))
    # most tokens forget little, some nearly everything: both ends inside one chunk
    g = low * rng.uniform(size=(B, S, Hk * r)) ** 3
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((B, S, Hk * r))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _recurrence(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    return delta_recurrence(jnp.repeat(q * SCALE, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta)


@functools.lru_cache(maxsize=None)
def run(form, chunk=None, grad=False):
    """The rule in one ``form`` (the reference's ``recurrence``, the
    ``kernels`` in interpret mode, the ``plain`` chunk algebra), or the
    gradients of its output summed under a weight, as ONE program at full
    precision that the cases of a chunk share."""
    f = {
        "recurrence": _recurrence,
        "kernels": lambda *a: gdn.gdn_chunked(*a, chunk=chunk, interpret=True),
        "plain": lambda *a: gdn.gdn_chunked_plain(*a, chunk=chunk),
    }[form]
    program = jax.jit(jax.grad(lambda weight, *a: jnp.sum(f(*a) * weight), argnums=range(1, 6)) if grad else f)

    def at_full_precision(*a):
        with jax.default_matmul_precision("highest"):
            return program(*a)

    return at_full_precision


def _weight(args, seed=5):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(args[2].shape), jnp.float32)


@pytest.mark.parametrize("r", [2, 1], ids=["two-value-heads-a-key-head", "one"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_kernels_agree_with_the_recurrence(chunk, r):
    args = _inputs(11, r)
    assert float(args[3].min()) < -55.0 and args[0].shape[1] // chunk >= 2  # unbounded decays, several chunks
    weight = _weight(args)
    want, want_grads = run("recurrence")(*args), run("recurrence", grad=True)(weight, *args)
    got, got_grads = run("kernels", chunk)(*args), run("kernels", chunk, grad=True)(weight, *args)
    assert float(jnp.max(jnp.abs(got - want))) < OUT_TOL
    for name, a, b in zip("qkvgb", got_grads, want_grads):
        assert a.shape == b.shape and float(jnp.max(jnp.abs(b))) > 1e-2, name
        assert float(jnp.max(jnp.abs(a - b))) < GRAD_TOL, name


@pytest.mark.parametrize("r", [2, 1], ids=["two-value-heads-a-key-head", "one"])
@pytest.mark.parametrize("chunk", [32, 64])
def test_plain_chunk_algebra_is_the_kernels(chunk, r):
    """What a model takes off the TPU: the same chunk function under a scan,
    differentiated by jax, so it also checks the hand-written backward
    against jax's own."""
    args = _inputs(12, r)
    weight = _weight(args, 6)
    assert float(jnp.max(jnp.abs(run("kernels", chunk)(*args) - run("plain", chunk)(*args)))) < 1e-6
    for a, b in zip(run("kernels", chunk, grad=True)(weight, *args), run("plain", chunk, grad=True)(weight, *args)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_a_decay_of_minus_sixty_leaves_float32_in_kdas_form_and_not_in_this_one():
    """``ops/kda.py`` factorises a channel's ``exp(G[t] - G[s])`` into two
    exponentials about a reference sixteen tokens away: at -60 a token that is
    ``exp(960)``.  Here the decay is the exponential of a difference that is
    never positive."""
    q, k, v, g, beta = _inputs(13, 1)
    g = jnp.full_like(g, LOW)
    theirs = kda.kda_chunked_plain(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk=64)
    assert not bool(jnp.all(jnp.isfinite(theirs)))
    with jax.default_matmul_precision("highest"):
        mine, want = gdn.gdn_chunked_plain(q, k, v, g, beta, chunk=64), _recurrence(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(mine)))
    assert float(jnp.max(jnp.abs(mine - want))) < OUT_TOL
    # and where KDA's form is at home (-1 a token on every channel: at its bound of -5 its own
    # factors of exp(80) cost it 1e-4) the two rules are one
    g = jnp.full_like(g, -1.0)
    with jax.default_matmul_precision("highest"):
        theirs = kda.kda_chunked_plain(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk=64)
        mine = gdn.gdn_chunked_plain(q, k, v, g, beta, chunk=64)
    assert float(jnp.max(jnp.abs(mine - theirs))) < 2e-5  # ``tests/test_kda.py``'s limit for that form


def test_value_heads_read_their_key_heads_query_and_key():
    """Value head ``j`` goes with key head ``j // 2``: the rule over two value
    heads a key head IS the rule over q and k repeated, head for head."""
    q, k, v, g, beta = _inputs(14, 2, S=64)
    with jax.default_matmul_precision("highest"):
        shared = gdn.gdn_chunked_plain(q, k, v, g, beta, chunk=32)
        repeated = gdn.gdn_chunked_plain(jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta, chunk=32)
    assert float(jnp.max(jnp.abs(shared - repeated))) < 1e-6
    # ... and not with the key heads taken in another order
    swapped = gdn.gdn_chunked_plain(q[:, :, ::-1], k[:, :, ::-1], v, g, beta, chunk=32)
    assert float(jnp.max(jnp.abs(shared - swapped))) > 1e-2


def test_bfloat16_operands_stay_near_the_recurrence():
    """The chip's types: bfloat16 q, k, v with the state, the decays and the
    inverse in float32.  8e-3 of the output's largest value is bfloat16's own
    rounding (2^-8) through a few products; the state in bfloat16 would read
    ten times that.  And it is far outside the float32 tolerance above (read: 1e-3)."""
    args = _inputs(15, 2)
    want = run("recurrence")(*args)
    q, k, v, g, beta = args
    got = gdn.gdn_chunked(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta, chunk=64, interpret=True
    )
    assert got.dtype == jnp.bfloat16
    off = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert 100 * OUT_TOL < off < 8e-3 * float(jnp.max(jnp.abs(want))) + 4e-3


@pytest.mark.parametrize(
    "change",
    [dict(S=96), dict(heads=3), dict(g_heads=2)],
    ids=["a-sequence-of-no-whole-chunks", "value-heads-no-multiple-of-key-heads", "a-decay-a-key-head"],
)
def test_shapes_that_do_not_fit_are_refused(change):
    q, k, v, g, beta = _inputs(16, 2, S=change.get("S", 128))
    if "heads" in change:
        v, g, beta = v[:, :, :3], g[:, :, :3], beta[:, :, :3]
    if "g_heads" in change:
        g = g[:, :, :2]
    with pytest.raises(ValueError):
        gdn.gdn_chunked(q, k, v, g, beta, chunk=64, interpret=True)
