"""FLOPs per token, MFU and roofline shares from shapes, and the three readers
that reach an architecture's counting through the cell (PR 66)."""

import json
import os

import pytest

from ftbench import flops, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = ["mistral-7b-v0.3-1x1", "mistral-7b-v0.3-2on1"]


def _config(name):
    with open(os.path.join(ROOT, "ftbench", "configs", name + ".json")) as f:
        return json.load(f)


def _arch(config):
    return spec.load_architecture(config["architecture"], os.path.join(ROOT, "ftbench"))


def shapes_of(config):
    return _arch(config).shapes(config)


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_agrees_with_the_model(name):
    config = _config(name)
    arch = _arch(config)
    assert flops.num_params(shapes_of(config)) == arch.model(config).num_params() == arch.num_params(config)


def test_mistral_7b_sizes():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    one_layer = (flops.matmul_params(dict(shapes, n_layers=1)) - 4096 * 32768)
    assert one_layer == 218_103_808  # 218.1 M a layer, as the issue reckons
    assert flops.num_params(dict(shapes, n_layers=32)) == pytest.approx(7.248e9, rel=1e-3)
    assert flops.num_params(shapes) == pytest.approx(1140.9e6, rel=1e-3)
    assert flops.num_params(shapes_of(_config("mistral-7b-v0.3-2on1"))) == pytest.approx(486.6e6, rel=1e-3)


def test_flops_per_token_is_6n_plus_attention():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    n = 4 * 218_103_808 + 4096 * 32768
    assert flops.train_flops_per_token(shapes, 2048) == 6.0 * n + 12.0 * 4 * 4096 * 2048


def test_mfu_from_shapes_and_peak():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    per_token = flops.train_flops_per_token(shapes, 2048)
    at_peak = 197e12 / per_token
    assert flops.mfu_pct(at_peak, shapes, 2048, "TPU v5 lite") == pytest.approx(100.0)
    assert flops.mfu_pct(at_peak / 2, shapes, 2048, "TPU v5 lite") == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_flash_roofline_is_compute_bound_at_2048():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    need_flops = flops.flash_step_flops(shapes, 1, 2048)
    need_bytes = flops.flash_step_bytes(shapes, 1, 2048)
    # 6 matmuls of 2*S*S*D per head, causal half, 32 heads, 4 layers
    assert need_flops == 4 * 6 * 2 * 2048 * 2048 * 128 * 32 * 0.5
    least = need_flops / 197e12
    share = flops.roofline_pct(need_flops, need_bytes, 2 * least, "TPU v5 lite")
    assert share["bound"] == "compute"
    assert share["pct"] == pytest.approx(50.0)


# -- architectures/llama.py gives the answers harness.py gave before PR 28 -----


@pytest.mark.parametrize("name,params_m,layers", [(CONFIGS[0], 1140.9, 4), (CONFIGS[1], 486.6, 1)])
def test_llama_architecture_counts_and_shapes_as_before(name, params_m, layers):
    config = _config(name)
    arch = _arch(config)
    assert arch.num_params(config) / 1e6 == pytest.approx(params_m, abs=0.05)
    # ``harness.shapes_of``'s dict, key for key
    assert arch.shapes(config) == dict(
        dim=4096, n_layers=layers, n_heads=32, n_kv_heads=8, ffn_hidden=14336, vocab_size=32768,
        rope_theta=1000000.0, norm_eps=1e-05,
    )
    assert arch.vocab(config) == 32768 and arch.KERNEL_PATHS == {"flash"} and arch.COARSE_RATIO_K == 3.9
    # ``harness.TOY_WIDTHS`` and ``TOY_SEQ``, value for value
    assert arch.TOY == dict(
        config=dict(hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
                    vocab_size=512, torch_dtype="float32"),
        seq_len=128,
    )
    cfg = arch.model_config(config)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.ffn_hidden, cfg.vocab_size) == (
        4096, layers, 32, 8, 14336, 32768)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq_len, cfg.dtype.__name__) == (1e6, 1e-5, 32768, "bfloat16")


def test_llama_architecture_reference_is_reference_py_bit_for_bit():
    import jax
    import numpy as np

    from ftbench import reference

    config = dict(_config(CONFIGS[0]), num_hidden_layers=2)
    arch = _arch(config)
    config.update(arch.TOY["config"])
    model = arch.model(config)
    assert type(model).__module__ == "torchft_tpu.models.llama"
    params = jax.tree_util.tree_map(np.asarray, model.init(jax.random.PRNGKey(11)))
    tokens = np.random.default_rng(12).integers(0, arch.vocab(config), size=(2, 64)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    ours = np.asarray(arch.token_nll(params, tokens, targets, config))
    theirs = np.asarray(reference.token_nll(params, tokens, targets, arch.shapes(config)))
    assert ours.shape == (2, 64) and ours.dtype == np.float32 and np.array_equal(ours, theirs)
    # and it is the model's own loss, in float32 to summation order
    assert float(ours.mean()) == pytest.approx(float(model.loss(params, (tokens, targets))), abs=2e-5)


# ----------------------------------------------------------------------
# PR 66: ``step_mfu_pct``, ``moe_gmm_roofline`` and ``flash_roofline`` are ONE
# reader each, which finds the counting of operations and bytes through the
# cell's architecture (``sources["architecture"].flops``), where nineteen forks
# had asked ``<their architecture>.is_mine(shapes)`` and counted with a class
# they named themselves.
#
# What a fork read is kept: before the forks went, each was run on the hand-made
# sources below (the cell's real ``shapes``, fixed seconds for the step and the
# kernels, fixed ``rows_here`` events) and its number written to
# ``data/fork_readings.json``.  The folded reader has to give that number.  Made
# again from the parent's files with
#
#     git archive 571d9ffb | tar -x -C _parent
#     python -m ftbench.tests.test_ftbench_flops --record _parent/ftbench
#
# (in THIS file because tier-1 collects ``tests/`` alone and
# ``tests/test_ftbench_flops.py`` imports it whole: a new file here would be
# collected by nobody, and a ``benchmark`` PR adds no file under ``tests/``)
# ----------------------------------------------------------------------

BENCH_DIR = os.path.join(ROOT, "ftbench")
READINGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "fork_readings.json")

# folded reader -> cell -> the fork that read the cell before PR 66 (None: the
# cell had no reader of the quantity, and the recording is the arithmetic by hand)
FORKS = {
    "step_mfu_pct": {
        "mistral7b-ws1-steady": "step_mfu_pct",
        "ling3flash-ws1-seq8k": "ling_step_mfu_pct",
        "keye2-ws1-seq16k": "dsa_step_mfu_pct",
        "nemotron3nano-ws1-seq16k": "ssm_step_mfu_pct",
        "trinitymini-ws1-seq16k": "swa_step_mfu_pct",
        "joyaiflash-ws1-seq16k": "latent_step_mfu_pct",
        "evabyte-ws1-seq32k": "eva_step_mfu_pct",
        "qwen3next-ws1-seq16k": "gdn_step_mfu_pct",
        "ouro2.6b-ws1-seq16k": "loop_step_mfu_pct",
        "phi4miniflash-ws1-seq16k": "sambay_step_mfu_pct",
    },
    "moe_gmm_roofline": {
        "ling3flash-ws1-seq8k": "moe_gmm_roofline",
        "keye2-ws1-seq16k": "dsa_moe_gmm_roofline",
        "nemotron3nano-ws1-seq16k": "ssm_moe_gmm_roofline",
        "trinitymini-ws1-seq16k": "swa_moe_gmm_roofline",
        "joyaiflash-ws1-seq16k": "latent_moe_gmm_roofline",
        "qwen3next-ws1-seq16k": None,
    },
    "flash_roofline": {
        "mistral7b-ws1-steady": "flash_roofline",
        "ling3flash-ws1-seq8k": "mla_flash_roofline",
        "nemotron3nano-ws1-seq16k": "ssm_flash_roofline",
        "trinitymini-ws1-seq16k": "swa_full_flash_roofline",
        "joyaiflash-ws1-seq16k": "latent_flash_roofline",
        "qwen3next-ws1-seq16k": "gdn_flash_roofline",
        "ouro2.6b-ws1-seq16k": "loop_flash_roofline",
    },
}
CASES = [(reader, cell) for reader, cells in FORKS.items() for cell in cells]
GONE = sorted({fork for reader, cells in FORKS.items() for fork in cells.values() if fork and fork != reader})
WS1_CELLS = sorted(FORKS["step_mfu_pct"])
ARCHITECTURES = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, "architectures"))
                       if f.endswith(".py") and not f.endswith("_reference.py"))

# seconds a step of the kernels a trace of the cell's architecture names
# (``torchft_tpu/obs/spans.py``, "Device operations with names of their own")
KERNELS = {
    "llama": ["flash_fwd", "flash_dq", "flash_dkv"],
    "ling_hybrid": ["kda_fwd", "kda_bwd", "flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm"],
    "indexed_sparse_moe": ["dsa_index", "dsa_select", "dsa_attn_fwd", "dsa_attn_dq", "dsa_attn_dkv", "dsa_probs", "gmm", "tgmm"],
    "ssm_hybrid_moe": ["ssd_fwd", "ssd_bwd", "flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm"],
    "windowed_moe": ["flash_win_fwd", "flash_win_dq", "flash_win_dkv", "flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm"],
    "latent_moe": ["flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm"],
    "eva": ["eva_fwd", "eva_dq", "eva_dkv"],
    "gated_delta_moe": ["gdn_fwd", "gdn_bwd", "flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm"],
    "looped": ["flash_fwd", "flash_dq", "flash_dkv"],
    "sambay": ["selscan_fwd", "selscan_bwd", "flash_win_fwd", "flash_win_dq", "flash_win_dkv", "flash_fwd", "flash_dq", "flash_dkv"],
}
STEP_S, FUSION_S = 8.0, 2.0
CALL = "%{}.{} = bf16[1,32,16384,128] custom-call(bf16[1,32,16384,128] %p), custom_call_target=\"tpu_custom_call\""


def hand_made_sources(cell_name, kernels=True):
    """What a reader is handed in a traced run of ``cell_name``, made by hand:
    two traced steps of eight seconds; in each 2 s of an XLA fusion, then the
    architecture's kernels one after the other, the k-th for 300 + 70 k ms, and an
    operation that only MENTIONS a kernel; the router's rows of the experts held
    a layer, another number a step.  ``kernels=False``: a trace without them."""
    cell = spec.load_cell(cell_name)
    arch, traffic, layout = cell.architecture, cell.traffic, cell.config["layout"]
    seq = traffic["seq_len"]
    tokens = layout["chips_per_group"] * traffic["sequences_per_chip"] * seq
    steps = [dict(step=7 + i, committed=True, t_enter=10.0 + STEP_S * i, t_exit=10.0 + STEP_S * (i + 1)) for i in range(2)]
    ops, events = [], []
    for i, step in enumerate(steps):
        at = step["t_enter"] + 0.1
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, FUSION_S))
        at += FUSION_S
        for k, kernel in enumerate(KERNELS[cell.config["architecture"]] if kernels else ()):
            ops.append((CALL.format(kernel, 3 + k), at, 0.30 + 0.07 * k))
            at += 0.30 + 0.07 * k
        ops.append(("%copy.8 = bf16[1,32,16384,128] copy(%flash_fwd.3, %gmm.9)", at, 0.001))
        rows = [1000.0 * (1 + i) + 64.0 * layer for layer in range(5)]
        events.append(dict(name="MOE_ROUTE", t=step["t_enter"] + 0.5, rows_here=rows))
    # an event from before the window does not count
    events.append(dict(name="MOE_ROUTE", t=1.0, rows_here=[9.0] * 5))
    return dict(
        cell=cell_name, architecture=arch, shapes=arch.shapes(cell.config), seq=seq,
        chips=cell.chips, replicas=traffic["replicas"], groups_share_chip=layout["groups_share_chip"],
        rows_per_replica=tokens // seq, tokens_per_step_per_replica=tokens, device_kind="TPU v5 lite",
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[events],
    )


def _by_hand(reader, sources):
    """A cell that had no fork: the reader's arithmetic written out."""
    assert reader == "moe_gmm_roofline"
    rows_here = (1000.0 + 128.0 + 2000.0 + 128.0) / 2  # the mean over two steps of the mean over five layers
    seconds = (0.30 + 0.07 * 5) + (0.30 + 0.07 * 6)  # gmm is the sixth kernel of KERNELS["gated_delta_moe"], tgmm the seventh
    need = sources["architecture"].gdn_flops.gmm_step(sources["shapes"], rows_here)
    return flops.roofline_pct(*need, seconds, sources["device_kind"])["pct"]


def record(forks_dir):
    """``data/fork_readings.json`` from the forks' files under ``forks_dir``
    (the parent's ``ftbench``), with the parent's helpers ``_*.py`` under the
    names the forks import them by: two of them went with the forks."""
    import importlib.util
    import sys

    import ftbench.layer_metrics as package

    folder = os.path.join(forks_dir, "layer_metrics")
    for helper in sorted(f[:-3] for f in os.listdir(folder) if f.startswith("_") and f.endswith(".py")):
        module_spec = importlib.util.spec_from_file_location("ftbench.layer_metrics." + helper, os.path.join(folder, helper + ".py"))
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        sys.modules[module_spec.name] = module
        setattr(package, helper, module)
    out = {}
    for reader, cell in CASES:
        fork, sources = FORKS[reader][cell], hand_made_sources(cell)
        if fork is None:
            value = _by_hand(reader, sources)
        else:
            sources.pop("architecture")  # a fork never asked for it
            value = spec.load_metric(fork, forks_dir).read(sources)
        assert value is not None and 0.0 < value < 100.0, (reader, cell, fork, value)
        out.setdefault(reader, {})[cell] = dict(fork=fork, value=value)
    with open(READINGS, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    return out


def _entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return next((m for m in json.load(f)["per_layer"] if m["name"] == name), None)


@pytest.fixture(scope="module")
def recorded():
    with open(READINGS) as f:
        return json.load(f)


@pytest.mark.parametrize("reader,cell", CASES)
def test_the_folded_reader_reads_what_the_fork_read(recorded, reader, cell):
    """(i) the recorded number to 1e-9, through the cell's architecture alone:
    the shapes of ANOTHER architecture under the same module change nothing a
    fork's ``is_mine`` would have refused, because nobody asks."""
    was = recorded[reader][cell]
    assert was["fork"] == FORKS[reader][cell]
    read = spec.load_metric(reader, BENCH_DIR).read
    assert read(hand_made_sources(cell)) == pytest.approx(was["value"], rel=1e-9)
    assert 0.0 < was["value"] < 100.0
    # and the entry lists the cell
    assert cell in _entry(reader)["workloads"]


@pytest.mark.parametrize("reader,cell", CASES)
def test_the_folded_reader_finds_nothing_where_there_is_nothing_to_read(reader, cell):
    """No trace (an untraced run, the CPU walk), no architecture in the sources
    (a harness from before PR 66), and for a kernel's share a trace without the
    kernels: None, never 0 and never an error."""
    read = spec.load_metric(reader, BENCH_DIR).read
    sources = hand_made_sources(cell)
    assert read(dict(sources, trace=None)) is None
    assert read({k: v for k, v in sources.items() if k != "architecture"}) is None
    if reader != "step_mfu_pct":
        assert read(hand_made_sources(cell, kernels=False)) is None
    if reader == "moe_gmm_roofline":
        assert read(dict(sources, flight=[[]])) is None and read(dict(sources, flight=None)) is None


def _names_a_full_flash_launch(cell):
    return "flash_fwd" in KERNELS[spec.load_cell(cell).config["architecture"]]


# ``phi4miniflash-ws1-seq16k`` is on no list of ``flash_roofline`` and still not here:
# ``flash_fwd`` runs in it, and ``sambay_flops.flash_step`` counts the windowed
# launches too, which is ``sambay_flash_roofline``'s quantity: there the lists alone guard
@pytest.mark.parametrize(
    "reader,cell",
    [("moe_gmm_roofline", cell) for cell in WS1_CELLS if cell not in FORKS["moe_gmm_roofline"]]
    + [("flash_roofline", cell) for cell in WS1_CELLS
       if cell not in FORKS["flash_roofline"] and not _names_a_full_flash_launch(cell)],
)
def test_the_folded_reader_reads_nothing_in_a_cell_of_another_list(reader, cell):
    """A cell whose architecture has no experts, or no launch called
    ``flash_fwd``: its entry does not list the cell, and on the cell's own
    sources the reader returns None."""
    assert cell not in _entry(reader)["workloads"]
    assert spec.load_metric(reader, BENCH_DIR).read(hand_made_sources(cell)) is None


@pytest.mark.parametrize("name", ARCHITECTURES)
def test_an_architecture_file_has_one_flops(name):
    """(ii) ``flops`` is the class the file already had, with ``is_mine`` and
    ``train_flops_per_token``, and ``gmm_step`` / ``flash_step`` where its cell
    is on ``moe_gmm_roofline``'s / ``flash_roofline``'s list.  By the lists of
    ``BENCHMARK.json`` and not by today's ten: a later architecture's file is
    one more case here and edits nothing."""
    arch = spec.load_architecture(name, BENCH_DIR)
    count = arch.flops
    assert isinstance(count, type) and getattr(arch, count.__name__) is count
    listed = {reader: _entry(reader)["workloads"] for reader in FORKS}
    by_architecture = {cell: spec.load_cell(cell) for cell in listed["step_mfu_pct"]}
    cells = [cell for cell, loaded in by_architecture.items() if loaded.config["architecture"] == name]
    assert cells, f"no cell of the architecture {name} is on step_mfu_pct's list"
    for cell in cells:
        loaded = by_architecture[cell]
        s, seq = arch.shapes(loaded.config), loaded.traffic["seq_len"]
        assert count.is_mine(s) and not count.is_mine(None) and not count.is_mine({})
        assert count.train_flops_per_token(s, seq) > 6.0 * 1e8
        assert (cell in listed["moe_gmm_roofline"]) == hasattr(count, "gmm_step"), cell
        if cell in listed["flash_roofline"]:
            operations, nbytes = count.flash_step(s, 1, seq)
            assert operations > 0 and nbytes > 0
        if hasattr(count, "gmm_step"):
            operations, nbytes = count.gmm_step(s, 1024.0)
            assert operations > 0 and nbytes > 0
    # no other architecture's shapes are this one's
    for other, theirs in by_architecture.items():
        if theirs.config["architecture"] != name:
            assert not count.is_mine(theirs.architecture.shapes(theirs.config)), other


def test_the_common_names_are_the_methods_the_classes_had():
    windowed = spec.load_architecture("windowed_moe", BENCH_DIR).flops
    ling = spec.load_architecture("ling_hybrid", BENCH_DIR).flops
    s = dict(dim=16, n_windowed=2, n_full=1, window=3, n_heads=4, n_kv_heads=2, head_dim=8)
    assert windowed.flash_step(s, 1.0, 8) == windowed.full_flash_step(s, 1.0, 8) != windowed.win_flash_step(s, 1.0, 8)
    cell = spec.load_cell("ling3flash-ws1-seq8k")
    s = cell.architecture.shapes(cell.config)
    assert ling.flash_step(s, 1, 8192) == ling.mla_flash_step(s, 1, 8192)


def test_a_group_of_two_chips_shares_its_rows_and_tokens_out():
    """``step_mfu_pct`` and ``flash_roofline`` on the four-chip cell's layout
    (two groups of two chips, which no list has today): a chip's share of a
    group's tokens and rows, as the readers counted before the fold."""
    sources = hand_made_sources("mistral7b-hsdp2x2-steady")
    assert (sources["chips"], sources["replicas"], sources["rows_per_replica"]) == (4, 2, 2)
    count, s, seq = sources["architecture"].flops, sources["shapes"], sources["seq"]
    busy = FUSION_S + 0.30 + 0.37 + 0.44 + 0.001
    mfu = spec.load_metric("step_mfu_pct", BENCH_DIR).read(sources)
    assert mfu == pytest.approx(100.0 * (seq / busy) * count.train_flops_per_token(s, seq) / 197e12)
    share = spec.load_metric("flash_roofline", BENCH_DIR).read(sources)
    assert share == pytest.approx(flops.roofline_pct(*count.flash_step(s, 1, seq), 1.11, "TPU v5 lite")["pct"])


@pytest.mark.parametrize("name", GONE)
def test_a_fork_is_gone(name):
    """No entry, no reader's file, and the benchmark's page does not name it."""
    assert _entry(name) is None
    assert spec.load_metric(name, BENCH_DIR) is None
    with open(os.path.join(BENCH_DIR, "README.md")) as f:
        assert f"`{name}`" not in f.read()


def test_room():
    """The fold's lists hold the cells the forks read (and whichever later
    cell has the quantity: no count of cells, and of entries only the cap)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert len(per_layer) <= 128
    assert all(m.get("workloads") for m in per_layer)
    for reader, cells in FORKS.items():
        assert set(cells) <= set(_entry(reader)["workloads"]), reader


if __name__ == "__main__":
    import sys

    assert sys.argv[1] == "--record", sys.argv
    print(json.dumps(record(sys.argv[2]), indent=1))
