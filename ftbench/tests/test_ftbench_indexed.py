"""The configuration ``keye-vl-2.0-30b-a3b-ep8-1x1``, its architecture file,
its counting of operations and bytes, its readers and the CPU rehearsal of
the cell ``keye2-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "keye2-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok", "sa_config", "rope_scaling",
)
SEQ = 16384
PICKED = 2048 * 2049 / 2 + (SEQ - 2048) * 2048  # sum_t min(t + 1, 2048)


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"])
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["moe_intermediate_size"], config["head_dim"]) == (2048, 768, 128)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["num_experts_per_tok"]) == (32, 4, 8)
    assert config["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1, kv_chunk_size=512,
        q_chunk_size=512, topk=2048,
    )
    assert config["rope_theta"] == 10_000_000 and config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    # the router keeps its width; the keys that count experts say how many are held
    assert config["router_experts"] == config["published"]["num_experts"] == 128
    assert config["experts_held"] == [0, config["num_experts"]] == [0, config["num_local_experts"]] == [0, 16]
    # the floors: four layers (a period is one), 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("index_loss_weight", "index_loss", "balance_loss_weight", "topk_counts_tokens", "index_rope",
                "index_key_norm", "index_weight_scale", "qk_norm", "rope", "router", "vision_tower",
                "learning_rate", "optimizer"):
        assert key in config["assumed"], key
    assert "INFERENCE" in config["assumed"]["topk_counts_tokens"]
    assert "8 chips share" in config["stands_for"] and "an eighth" in config["stands_for"]
    assert "parameters_here" in config
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    layers = config["num_hidden_layers"]
    layer = 18_874_368 + 256 + 2_260_992 + 262_144 + 16 * 4_718_592 + 4096
    assert arch.num_params(config) == layers * layer + 2 * 18_992 * 2048 + 2048
    assert arch.vocab(config) == 18_992 and arch.KERNEL_PATHS == {"dsa"}
    s = arch.shapes(config)
    count = arch.dsa_flops
    assert count.is_mine(s) and not count.is_mine(spec.load_cell("mistral7b-ws1-steady").architecture.shapes(
        spec.load_cell("mistral7b-ws1-steady").config))
    # ISSUE 33: a token's matmul parameters a layer: attention 18.87 M, index 2.26 M,
    # router 0.26 M, one expert of the 16 held on average 4.72 M: 26.1 M
    per_layer = (count.matmul_params_touched(s) - 2048 * 18_992) / layers
    assert per_layer == pytest.approx(26.1e6, rel=2e-3)
    assert count.picked_pairs(s, SEQ) == PICKED and PICKED / SEQ == 1920.0625
    assert count.picked_pairs(s, 1024) == count.causal_pairs(1024)  # under topk: every causal pair
    index_flops, index_bytes = count.index_step(s, 1, SEQ)
    assert index_flops == layers * 2 * 16 * 64 * SEQ * (SEQ + 1) / 2
    assert index_flops / layers / SEQ == pytest.approx(16.8e6, rel=1e-2)  # ISSUE 33: 16.8 MFLOP a query
    attn_flops, attn_bytes = count.attn_step(s, 1, SEQ)
    assert attn_flops == layers * 12 * 128 * 32 * PICKED
    assert attn_flops / 3 / layers / SEQ == pytest.approx(31.5e6, rel=1e-2)  # ISSUE 33: 31.5 MFLOP forward
    assert attn_bytes > layers * 3 * SEQ * SEQ / 8  # at least the bits, three times
    gmm_flops, gmm_bytes = count.gmm_step(s, 16384.0)
    assert gmm_flops == layers * 9 * 2 * 2048 * 768 * 16384
    assert gmm_bytes > layers * 3 * 16 * 3 * 2048 * 768 * 2  # at least the held weights three times
    per_token = count.train_flops_per_token(s, SEQ)
    assert 6 * count.matmul_params_touched(s) < per_token < 6 * count.matmul_params_touched(s) + layers * 0.2e9


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell):
    call = "%{} = bf16[1,32,16384,128] custom-call(bf16[1,32,16384,128] %p), custom_call_target=tpu_custom_call"
    layers = cell.config["num_hidden_layers"]
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops += [
            ("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 0.2),
            (call.format("dsa_index.3"), at + 0.20, 0.030), (call.format("dsa_select.4"), at + 0.23, 0.080),
            (call.format("dsa_attn_fwd.20"), at + 0.31, 0.300), (call.format("dsa_attn_fwd.21"), at + 0.61, 0.300),
            (call.format("dsa_attn_dq.10"), at + 0.91, 0.200), (call.format("dsa_attn_dkv.10"), at + 1.11, 0.250),
            (call.format("dsa_probs.20"), at + 1.36, 0.170), (call.format("dsa_probs.21"), at + 1.53, 0.170),
            (call.format("jvp_jit_gmm__.2"), at + 1.70, 0.100),
            (call.format("transpose_jvp_jit_tgmm___.7"), at + 1.80, 0.050),
            # operations that only MENTION a kernel, as their operand
            ("%get-tuple-element.3 = f32[8] get-tuple-element(%dsa_select.4), index=1", at + 1.85, 0.001),
            ("%copy.8 = bf16[16384,128] copy(%dsa_attn_fwd.20)", at + 1.86, 0.001),
        ]
    event = lambda t, rows, keys: dict(  # noqa: E731
        name="MOE_ROUTE", t=t, rows_here=[rows] * layers, load_max=[2 * rows / 16] * layers,
        load_mean=[rows / 16] * layers, index_kl=[0.5] * layers, keys_per_query=[keys] * layers,
    )
    flight = [event(2.9, 16384.0, 1920.0625), event(4.9, 20480.0, 1920.0625), event(0.5, 9.0, 9.0)]
    return _trace_sources(cell, ops, flight)


def test_kernel_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("dsa_index_ms") == pytest.approx(30.0) and read("dsa_select_ms") == pytest.approx(80.0)
    assert read("dsa_attn_ms") == pytest.approx(1050.0) and read("dsa_probs_ms") == pytest.approx(340.0)
    assert read("moe_gmm_ms") == pytest.approx(150.0)
    count, s = cell.architecture.dsa_flops, sources["shapes"]
    from ftbench import flops

    for name, need, seconds in (
        ("dsa_index_roofline", count.index_step(s, 1, SEQ), 0.030),
        ("dsa_attn_roofline", count.attn_step(s, 1, SEQ), 1.050),
        ("moe_gmm_roofline", count.gmm_step(s, 18432.0), 0.150),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    assert read("dsa_keys_per_query") == 1920.0625
    layers = cell.config["num_hidden_layers"]
    assert read("moe_rows_here_per_step") == pytest.approx(layers * 18432.0)
    assert read("moe_load_max_over_mean") == pytest.approx(2.0)
    busy = 0.2 + 0.03 + 0.08 + 1.05 + 0.34 + 0.15 + 0.002  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(
        100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12
    )
    # no launch called ``flash_fwd`` and no ``flash_step``: the third folded reader finds nothing, nor another architecture's
    assert read("flash_roofline") is None and read("kda_roofline") is None


NEW_READERS = (
    "dsa_index_ms", "dsa_select_ms", "dsa_attn_ms", "dsa_probs_ms", "dsa_index_roofline",
    "dsa_attn_roofline", "dsa_keys_per_query",
)  # ``dsa_moe_gmm_roofline`` and ``dsa_step_mfu_pct`` were two more until PR 66: the cell is on the folded readers' lists


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such kernel, flight field or architecture:
    the reader returns None, never raises, and the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        read = spec.load_metric(name, BENCH_DIR).read
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="keye-vl-2.0-30b-a3b-ep8-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("tokens_per_s_per_chip", "quorum_ms", "commit_vote_ms", "step_device_ms", "device_idle_pct",
                 "peak_hbm_gb", "moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean",
                 "step_mfu_pct", "moe_gmm_roofline"):
        assert CELL in listed[name], name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)
    with open(os.path.join(BENCH_DIR, "traffic", "ws1-seq8k.json")) as f:
        older = json.load(f)
    assert traffic["lighthouse"] == older["lighthouse"] and traffic["manager"] == older["manager"]


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean", "dsa_keys_per_query"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary in the flight events, the float32 reference, the
    readers."""
    done = _run(["--workload", CELL, "--seed", "3000000041", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"dsa_attn_ms", "dsa_attn_roofline", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.1444, abs=1e-3)
