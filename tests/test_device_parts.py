"""The parts of the compiled step (``torchft_tpu/obs/spans.py``,
``DEVICE_PARTS``): every operation that costs device time in the twelve models'
two step programs is traced under a ``tpuft.<part>`` scope, at toy widths and
on both paths (plain, and the kernels in interpret mode).  The paths are read
from the COMPILED text's ``op_name``s: XLA inlines every private function
there, so a path is whole (the lowered module's locations are relative to the
function an operation stands in)."""

import collections
import re

import pytest

from torchft_tpu.obs.spans import DEVICE_PARTS, PART_PREFIX, part

from tests._toys import step_texts, toy

MODELS = ("llama", "ling_hybrid", "indexed_sparse_moe", "ssm_hybrid_moe", "windowed_moe", "latent_moe", "eva", "gated_delta_moe", "looped", "sambay", "prerouted_moe", "ssm_hybrid_dense")
CASES = [(m, p) for m in MODELS for p in ("plain", "kernels")]
EVERY = set(DEVICE_PARTS)
# Keye has no dense MLP and no shared expert; Mistral has no experts; the
# prediction module's own work is ``mtp``, and JoyAI's is the one model here
# that runs the module (Ling's toy preset builds none); EvaByte's is the one
# mixer that pools, the looped model's the one exit gate, and SambaY's the one
# model that subtracts two softmaxes; SmallThinker, like Keye, has no dense MLP
# and no shared expert; granite's is the one model whose mixer names its
# convolution and its gated norm apart from the glue around them
USES = {
    "llama": EVERY - {"experts_route", "experts_dispatch", "mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "ling_hybrid": EVERY - {"mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "indexed_sparse_moe": EVERY - {"ffn", "mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "ssm_hybrid_moe": EVERY - {"mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "windowed_moe": EVERY - {"mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "latent_moe": EVERY - {"mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "eva": EVERY - {"experts_route", "experts_dispatch", "mtp", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "gated_delta_moe": EVERY - {"mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "looped": EVERY - {"experts_route", "experts_dispatch", "mtp", "mixer_pool", "mixer_diff", "mixer_conv", "mixer_gate"},
    "sambay": EVERY - {"experts_route", "experts_dispatch", "mtp", "mixer_pool", "loop_gate", "mixer_conv", "mixer_gate"},
    "prerouted_moe": EVERY - {"ffn", "mtp", "mixer_pool", "loop_gate", "mixer_diff", "mixer_conv", "mixer_gate"},
    "ssm_hybrid_dense": EVERY - {"experts_route", "experts_dispatch", "mtp", "mixer_pool", "loop_gate", "mixer_diff"},
}
# what costs time on a device and is never fused away into a neighbour
HELD = ("dot", "convolution", "gather", "scatter", "sort")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(HELD) + r")\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PART = re.compile(re.escape(PART_PREFIX) + r"(\w+)")


def innermost(path):
    """The part of an operation from its path: the last ``tpuft.<name>``."""
    found = _PART.findall(path or "")
    return found[-1] if found else None


def _paths(text):
    """(every instruction's path, [(opcode, path) of the held instructions]).
    A path begins at the program (``jit(``): a parameter is named after its
    leaf, and the adder inside a ``reduce`` after the reduce alone."""
    every, held = [], []
    for line in text.splitlines():
        op_name = _OP_NAME.search(line)
        if op_name and op_name.group(1).startswith("jit("):
            every.append(op_name.group(1))
        m = _INSTRUCTION.match(line)
        if m:
            held.append((m.group(1), op_name.group(1) if op_name else None))
    return every, held


def _compiled_steps(name, path):
    """The two step programs' compiled text, made once a run of the tests
    with the text ``test_lowered_steps.py`` reads (``_toys.step_texts``), so
    at that file's sequence lengths; ``llama`` rematerialised is this file's
    own."""
    texts = step_texts("llama_remat" if name == "llama" else name, path)
    return texts["grad"], texts["update"]


@pytest.mark.parametrize("name,path", CASES)
def test_every_costly_operation_has_a_part(name, path):
    grad, update = _compiled_steps(name, path)
    every, held = _paths(grad)
    assert len(held) >= 20, "the gradient step's text holds no instructions this test can read"
    # an instruction with NO path at all is the CPU compiler's own (it rewrites
    # a batched product, the plain paths' chunk algebra above all, into a new
    # one and drops the metadata): nothing a scope could reach, and the same
    # products are held on the other path, inside the interpreted kernels
    named = [(op, p) for op, p in held if p is not None]
    # (granite's toy is the chunk algebra in nine layers of ten, four heads unrolled: a third is named)
    assert len(named) >= (0.3 if name == "ssm_hybrid_dense" else 0.5) * len(held), (len(named), len(held))
    by_part = collections.Counter(innermost(p) for _, p in named)
    nameless = [(op, p) for op, p in named if innermost(p) not in EVERY - {"layers"}]
    assert not nameless, f"{len(nameless)} of {len(named)} without a part of their own: {nameless[:5]}"
    # the passes show in the path: forward, backward, and forward run again
    assert any("jvp(" in p and "transpose(" not in p for p in every)
    assert any("transpose(" in p for p in every)
    assert any("rematted_computation" in p for p in every)
    used = {innermost(p) for p in every} - {None}
    assert used == USES[name] - {"optimizer"}, (sorted(used), dict(by_part))
    # the update step is one part, whole
    every, held = _paths(update)
    assert every and {innermost(p) for p in every} == {"optimizer"}


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_poolings_operations_are_under_their_part_and_nothing_elses_is(path):
    """``tpuft.mixer_pool`` is ``Eva._pool``, whole and alone.  The function
    compiled BY ITSELF, forward and backward, has every operation under the
    part; in the gradient step the part is entered from the mixer's glue
    alone, forward, rematerialised and backward, and holds no primitive that
    the function by itself does not (rope's angles, a projection, the joining
    of the two key sources stay the glue's and the projections')."""
    import jax
    import jax.numpy as jnp

    model, _ = toy("eva")
    cfg = model.config
    k = jax.ShapeDtypeStruct((1, 64, cfg.n_heads, cfg.head_dim), jnp.float32)
    vector = jax.ShapeDtypeStruct((cfg.n_heads, cfg.head_dim), jnp.float32)
    total = lambda *a: sum(jnp.sum(x) for x in model._pool(*a))  # noqa: E731
    alone = []
    for program in (jax.jit(model._pool), jax.jit(jax.grad(total, argnums=(0, 1, 2, 3)))):
        every, _ = _paths(program.lower(k, k, vector, vector).compile().as_text())
        alone += [p for p in every if "tpuft." in p or p.count("/") > 1]
    assert len(alone) >= 10 and all(innermost(p) == "mixer_pool" for p in alone), [p for p in alone if innermost(p) != "mixer_pool"][:5]
    tail = lambda p: p.rstrip(":").rsplit("/", 1)[-1]  # noqa: E731
    its_own = {tail(p) for p in alone}
    every, _ = _paths(_compiled_steps("eva", path)[0])
    under = [p for p in every if innermost(p) == "mixer_pool"]
    assert under and all("tpuft.mixer_glue/tpuft.mixer_pool/" in p for p in under)
    assert {tail(p) for p in under} <= its_own, sorted({tail(p) for p in under} - its_own)
    assert not its_own & {"cos", "sin", "dot_general", "concatenate", "pallas_call"}
    for which in ("jvp(", "transpose(", "rematted_computation"):
        assert any(which in p for p in under), which
    # rope is there, and the glue's
    assert any(tail(p) in ("cos", "sin") and innermost(p) == "mixer_glue" for p in every)


def test_the_vocabulary_is_closed():
    assert len(DEVICE_PARTS) == len(set(DEVICE_PARTS)) == 16
    with pytest.raises(ValueError, match="nonsense"):
        part("nonsense")
    for name in DEVICE_PARTS:
        with part(name) as stack:
            assert str(stack).endswith(PART_PREFIX + name)


@pytest.mark.parametrize(
    "path,expects",
    [
        ("jit(_step)/jvp(tpuft.layers)/while/body/closed_call/tpuft.mixer_glue/tpuft.mixer_proj/dot_general", "mixer_proj"),
        ("jit(_step)/transpose(jvp(tpuft.layers))/while/body/dynamic_update_slice", "layers"),
        ("jit(_update)/tpuft.optimizer/mul", "optimizer"),
        # the module's layer names its own parts; the module's own work is its part
        ("jit(_step)/jvp(tpuft.mtp)/tpuft.stream/add", "stream"),
        ("jit(_step)/transpose(jvp(tpuft.mtp))/dot_general", "mtp"),
        # the pooling inside the glue is the pooling's
        ("jit(_step)/jvp(tpuft.layers)/while/body/checkpoint/tpuft.mixer_glue/tpuft.mixer_pool/reduce_sum", "mixer_pool"),
        # the exit gate and what it weighs are the gate's; a pass's head stays the head's
        ("jit(_step)/transpose(jvp(tpuft.loop_gate))/mul", "loop_gate"),
        ("jit(_step)/jvp(tpuft.head)/while/body/checkpoint/dot_general", "head"),
        # the two softmaxes' combination inside the glue is its own
        ("jit(_step)/jvp(tpuft.layers)/while/body/checkpoint/tpuft.mixer_glue/tpuft.mixer_diff/sub", "mixer_diff"),
        # a Mamba-2 mixer's convolution and its gated norm inside the glue are their own; softplus stays the glue's
        ("jit(_step)/transpose(jvp(tpuft.layers))/while/body/checkpoint/tpuft.mixer_glue/tpuft.mixer_conv/mul", "mixer_conv"),
        ("jit(_step)/jvp(tpuft.layers)/while/body/checkpoint/tpuft.mixer_glue/tpuft.mixer_gate/rsqrt", "mixer_gate"),
        ("jit(_step)/jvp(tpuft.layers)/while/body/checkpoint/tpuft.mixer_glue/softplus", "mixer_glue"),
        ("jit(_step)/concatenate", None),
        ("", None),
        (None, None),
    ],
)
def test_innermost_scope_of_a_path(path, expects):
    assert innermost(path) == expects
