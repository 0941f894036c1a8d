"""The parts of the compiled step (``torchft_tpu/obs/spans.py``,
``DEVICE_PARTS``): every operation that costs device time in the five models'
two step programs is traced under a ``tpuft.<part>`` scope, at toy widths and
on both paths (plain, and the kernels in interpret mode).  The paths are read
from the COMPILED text's ``op_name``s: XLA inlines every private function
there, so a path is whole (the lowered module's locations are relative to the
function an operation stands in)."""

import collections
import dataclasses
import os
import re

import pytest

from torchft_tpu.obs.spans import DEVICE_PARTS, PART_PREFIX, part

MODELS = ("llama", "ling_hybrid", "indexed_sparse_moe", "ssm_hybrid_moe", "windowed_moe")
CASES = [(m, p) for m in MODELS for p in ("plain", "kernels")]
EVERY = set(DEVICE_PARTS)
# Keye has no dense MLP and no shared expert; Mistral has no experts
USES = {
    "llama": EVERY - {"experts_route", "experts_dispatch"},
    "ling_hybrid": EVERY,
    "indexed_sparse_moe": EVERY - {"ffn"},
    "ssm_hybrid_moe": EVERY,
    "windowed_moe": EVERY,
}
# what costs time on a device and is never fused away into a neighbour
HELD = ("dot", "convolution", "gather", "scatter", "sort")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(HELD) + r")\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PART = re.compile(re.escape(PART_PREFIX) + r"(\w+)")


def _model(name):
    if name == "llama":
        from torchft_tpu.models.llama import Llama, llama_debug

        return Llama(dataclasses.replace(llama_debug(), remat=True)), 128
    if name == "ling_hybrid":
        from torchft_tpu.models.ling_hybrid import LingHybrid, ling_debug

        return LingHybrid(ling_debug()), 128
    if name == "indexed_sparse_moe":
        from torchft_tpu.models.indexed_sparse_moe import IndexedSparseMoE, indexed_sparse_debug

        return IndexedSparseMoE(indexed_sparse_debug()), 32
    if name == "windowed_moe":
        from torchft_tpu.models.windowed_moe import WindowedMoE, windowed_moe_debug

        return WindowedMoE(windowed_moe_debug()), 128
    from torchft_tpu.models.ssm_hybrid_moe import SsmHybridMoE, ssm_hybrid_debug

    return SsmHybridMoE(ssm_hybrid_debug()), 128


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
    import jax
    import numpy as np
    import optax

    from torchft_tpu.parallel.hsdp import make_grad_step, make_update_step
    from torchft_tpu.parallel.mesh import make_mesh

    before = os.environ.get("TORCHFT_FLASH")
    os.environ["TORCHFT_FLASH"] = "1" if path == "kernels" else "0"
    try:
        model, seq = _model(name)
        mesh = make_mesh(fsdp=1, devices=jax.devices()[:1])
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((1, seq), np.int32)
        grad = make_grad_step(model, mesh).lower(params, (tokens, tokens)).compile().as_text()
        assert ("naive" in model.attention_path or "plain" in model.attention_path) == (path == "plain")
        tx = optax.adamw(1e-3)
        update = make_update_step(model, tx, mesh).lower(params, jax.eval_shape(tx.init, params), params)
        return grad, update.compile().as_text()
    finally:
        if before is None:
            del os.environ["TORCHFT_FLASH"]
        else:
            os.environ["TORCHFT_FLASH"] = before


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
    assert len(named) >= 0.5 * len(held), (len(named), len(held))
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


def test_the_vocabulary_is_closed():
    assert len(DEVICE_PARTS) == len(set(DEVICE_PARTS)) == 10
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
        ("jit(_step)/concatenate", None),
        ("", None),
        (None, None),
    ],
)
def test_innermost_scope_of_a_path(path, expects):
    assert innermost(path) == expects
