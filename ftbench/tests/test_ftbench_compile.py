"""Each cell's step compiled at its real size for a described v5e:2x2.

No chip is needed: the TPU's compiler is installed and compiles for a chip
that is described and not attached.  The topology is described inside a
fixture (only the worker that runs this file loads libtpu), and nothing here
is a chip run: it shows that the compiler takes the programs and what it
says they need, not how fast they are.
"""

import json
import os

import pytest

from ftbench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without the chip; keep these out of it
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)
# every configuration BENCHMARK.json has, so one that a later PR adds is
# compiled for the chip here too, through its own architecture.  Since PR 43
# ``mistral-7b-v0.3-2x2`` (two groups of two chips, ``fsdp`` 2, depth 2) is
# one of them: the file that ships, where a pseudo-configuration stood
CONFIG_NAMES = [c["name"] for c in _BENCH["configs"]]


def _cell(config_name):
    """(architecture module, model, configuration, sequence length) through
    the lookup a run takes: a configuration's cells."""
    cells = [spec.load_cell(w["name"]) for w in _BENCH["workloads"] if w["config"] == config_name]
    arch, config = cells[0].architecture, dict(cells[0].config)
    return arch, arch.model(config), config, max(c.traffic["seq_len"] for c in cells)


def test_a_group_of_several_chips_is_among_the_configurations():
    """``test_step_compiles_for_v5e`` looks for collectives only where a group
    has more than one chip: one configuration at least has such a group."""
    assert any(spec.load_cell(w["name"]).config["layout"]["chips_per_group"] > 1 for w in _BENCH["workloads"])


def _compile_step(topo, config_name, monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.parallel.hsdp import fsdp_shardings, make_grad_step, make_update_step
    from torchft_tpu.parallel.mesh import make_mesh

    # the model asks jax.default_backend(), which is the CPU here
    monkeypatch.setenv("TORCHFT_FLASH_PLATFORM", "tpu")
    arch, model, config, seq = _cell(config_name)
    per_group = config["layout"]["chips_per_group"]
    mesh = make_mesh(fsdp=per_group, devices=list(topo.devices)[:per_group])
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, params_sh
    )
    batch = tuple(jax.ShapeDtypeStruct((per_group, seq), jnp.int32, sharding=sh) for sh in batch_sh)
    grad = make_grad_step(model, mesh).lower(params, batch).compile()
    assert model.attention_path in arch.KERNEL_PATHS
    assert "tpu_custom_call" in grad.as_text()
    tx = optax.adamw(config["assumed"]["learning_rate"])
    opt_shapes = jax.eval_shape(tx.init, shapes)
    update = make_update_step(model, tx, mesh).lower(params, opt_shapes, params).compile()
    return grad, update, per_group


@pytest.mark.parametrize("config_name", CONFIG_NAMES)
def test_step_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):
    grad, update, per_group = _compile_step(topo, config_name, monkeypatch)
    for program in (grad, update):
        need = program.memory_analysis()
        # a donated buffer is an argument AND the output that aliases it (the
        # update step's parameters and both moments): one buffer, counted once
        held = need.argument_size_in_bytes + need.output_size_in_bytes - need.alias_size_in_bytes
        total = held + need.temp_size_in_bytes
        assert total < HBM_BYTES, f"{config_name}: {total / 1e9:.1f} GB on a chip"
    if per_group > 1:
        # FSDP over ICI: the compiler put collectives into the step
        text = grad.as_text()
        assert "all-gather" in text or "all-reduce" in text or "reduce-scatter" in text


@pytest.mark.parametrize("config_name", CONFIG_NAMES)
def test_forward_check_compiles_for_v5e(topo, no_compile_cache, monkeypatch, config_name):
    """What runs after the window: weights from the seed, the coarse copy
    (to float8_e4m3fn in one program, back in another, laid out as the
    parameters are) and the forward pass that the program and the copy are
    both judged by."""
    import jax
    import jax.numpy as jnp

    from ftbench.harness import from_e4m3, system_token_nll, to_e4m3
    from torchft_tpu.parallel.hsdp import fsdp_shardings
    from torchft_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("TORCHFT_FLASH_PLATFORM", "tpu")
    arch, model, config, seq = _cell(config_name)
    per_group = config["layout"]["chips_per_group"]
    mesh = make_mesh(fsdp=per_group, devices=list(topo.devices)[:per_group])
    params_sh, batch_sh = fsdp_shardings(model, mesh)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, params_sh
    )
    batch = tuple(jax.ShapeDtypeStruct((per_group, seq), jnp.int32, sharding=sh) for sh in batch_sh)
    with mesh:
        init = jax.jit(model.init, out_shardings=params_sh).lower(jax.random.PRNGKey(0)).compile()
        down = jax.jit(to_e4m3).lower(params).compile()
        params8 = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(to_e4m3, shapes), params_sh,
        )
        up = jax.jit(from_e4m3, out_shardings=params_sh).lower(params8, params).compile()
        loss = jax.jit(model.loss).lower(params, batch).compile()
        nll = jax.jit(lambda p, b: system_token_nll(model, p, b)).lower(params, batch).compile()
    assert model.attention_path in arch.KERNEL_PATHS
    # the float8 leaves lie in memory between the two programs: a cast there
    # and back inside one fusion rounds nothing on this chip
    assert any(x.dtype == jnp.float8_e4m3fn for x in jax.tree_util.tree_leaves(params8))
    weights = up.memory_analysis().output_size_in_bytes
    assert down.memory_analysis().output_size_in_bytes < 0.51 * weights
    # the state a window leaves (parameters and two bf16 moments, twice where
    # two replicas share the chip), the seeded weights, the float8 leaves,
    # the copy and the pass fit
    state = 3 * weights * (2 if config["layout"]["groups_share_chip"] else 1)
    for program in (init, down, up, loss, nll):
        need = program.memory_analysis()
        assert state + 2.5 * weights + need.temp_size_in_bytes < HBM_BYTES
