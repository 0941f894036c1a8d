"""The configuration ``trinity-mini-ep8-1x1``, its architecture file, its
counting of operations and bytes, its readers and the CPU rehearsal of the
cell ``trinitymini-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "trinitymini-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_experts_per_tok", "sliding_window", "num_shared_experts",
)
SEQ = 16384
S, F = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"])
    assert sorted(config["reduced"]) == ["layer_types", "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]) == (2048, 6144, 1024)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (32, 4, 128)
    assert (config["sliding_window"], config["rope_theta"], config["rms_norm_eps"]) == (2048, 10000, 1e-5)
    assert (config["num_experts_per_tok"], config["route_scale"], config["route_norm"], config["score_func"]) == (8, 2.826, True, "sigmoid")
    assert (config["mup_enabled"], config["tie_word_embeddings"], config["load_balance_coeff"]) == (True, False, 0.001)
    # the list is the published one's first eight entries: two whole periods, three windowed layers to a full one
    published = config["published"]["layer_types"]
    assert published == [S, S, S, F] * 8 and config["layer_types"] == published[:8]
    assert config["num_hidden_layers"] == 8 and config["num_dense_layers"] == 1  # seven expert layers: over the floor of four
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == config["published"]["num_experts"] == 128
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 16]
    # the floors: 8 experts, an eighth of the vocabulary
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("learning_rate", "optimizer", "embedding_scale", "gated_attention", "qk_norm", "rope", "window",
                "sandwich_norms", "bias_update_rate", "bias_update", "balance_loss_weight", "router",
                "residual_stream", "weights", "kernels"):
        assert key in config["assumed"], key
    # what only the family's modelling code gives is marked with its origin
    for key in ("embedding_scale", "gated_attention", "qk_norm", "rope", "window", "sandwich_norms", "bias_update"):
        assert config["assumed"][key].startswith("(afmoe)"), key
    assert config["assumed"]["learning_rate"] == 1e-6 and config["assumed"]["balance_loss_weight"] == 0.0
    assert "8 chips share" in config["stands_for"] and "1,024 tokens" in config["stands_for"] and "8,192" in config["stands_for"]
    assert config["parameters_here"].startswith("1,108.94 M")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 1_108_939_648
    assert arch.vocab(config) == 25_024 and arch.KERNEL_PATHS == {"flash_win+flash"}
    s = arch.shapes(config)
    assert (s["n_windowed"], s["n_full"], s["n_dense"], s["n_moe"], s["window"]) == (6, 2, 1, 7, 2048)
    count = arch.swa_flops
    other = spec.load_cell("nemotron3nano-ws1-seq16k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config))
    # ISSUE 41: a token's matmul parameters: attention 27.26 M a layer (q, gate, o 8.39 M each; k, v 1.05 M each),
    # the dense layer 37.75 M, an expert layer 6.29 M shared + 0.26 M router + 8 x 6.29 M x 16/128 routed, the head 51.25 M
    attention, dense, head = 3 * 2048 * 4096 + 2 * 2048 * 512, 3 * 2048 * 6144, 2048 * 25024
    moe = 2048 * 128 + 3 * 2048 * 1024 + 8 * 16 / 128 * 3 * 2048 * 1024
    assert count.matmul_params_touched(s) == 8 * attention + dense + 7 * moe + head
    assert (attention, dense, head, moe) == (27_262_976, 37_748_736, 51_249_152, 12_845_056)
    # the live pairs: a window of 2,048 at 16,384 positions leaves 23 % of the causal pairs
    assert count.live_pairs(SEQ, 2048) == SEQ * 2048 - 2048 * 2047 / 2
    assert count.live_pairs(SEQ, None) == count.live_pairs(SEQ, SEQ) == count.live_pairs(SEQ, 10 * SEQ) == SEQ * (SEQ + 1) / 2
    assert count.live_pairs(SEQ, 2048) / count.live_pairs(SEQ) == pytest.approx(0.2344, abs=1e-4)
    assert count.live_pairs(8, 1) == 8 and count.live_pairs(8, 3) == 1 + 2 + 6 * 3


def test_counting_by_hand_at_toy_widths(cell):
    """``swa_flops`` against a count by hand: 4 heads of 8 over 2, a window of
    3 over 8 positions; two windowed layers and one full, one dense, two of experts."""
    count = cell.architecture.swa_flops
    s = dict(dim=16, n_windowed=2, n_full=1, n_dense=1, n_moe=2, window=3, n_heads=4, n_kv_heads=2, head_dim=8,
             dense_hidden=24, expert_hidden=12, shared_hidden=12, router_experts=8, experts_held=2, top_k=2, vocab_size=32)
    # attention: six products of 2 D a LIVE pair a query head; a window of 3 over 8 rows: 1 + 2 + 6 x 3 = 21 pairs
    operations, nbytes = count.win_flash_step(s, rows=1.0, seq=8)
    assert operations == 2 * (6 * 2 * 21 * 8 * 4)
    moved = 8 * 8 * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) * 2  # q, o (4 heads) and k, v (2) of 8, forward and backward
    assert nbytes == 2 * moved
    # a full layer: the causal half, 8 x 9 / 2 = 36 pairs; the same operands moved
    operations, nbytes = count.full_flash_step(s, rows=1.0, seq=8)
    assert operations == 6 * 2 * 36 * 8 * 4 and nbytes == moved
    # experts: THREE products forward and six backward a row; three matrices an expert
    operations, nbytes = count.gmm_step(s, rows_here=10.0)
    assert operations == 2 * (9 * 2 * 16 * 12 * 10)
    assert nbytes == 2 * (3 * 2 * (3 * 16 * 12) * 2 + 3 * 10 * (3 * 16 + 3 * 12) * 2)
    attention = 3 * 16 * 32 + 2 * 16 * 16
    params = 3 * attention + 3 * 16 * 24 + 2 * (16 * 8 + 3 * 16 * 12 + 2 * 2 / 8 * 3 * 16 * 12) + 16 * 32
    assert count.matmul_params_touched(s) == params
    both = count.win_flash_step(s, 1.0, 8)[0] + count.full_flash_step(s, 1.0, 8)[0]
    assert count.train_flops_per_token(s, 8) == 6 * params + both / 8


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, masked=False):
    """Two steps as the chip's trace names them: six layers' ``flash_win_*``
    (10, 8 and 12 ms a layer; ``masked``: as long as a full layer's), two
    layers' ``flash_*`` (40, 30 and 45 ms), the grouped products, and
    operations that only MENTION a kernel."""
    call = "%{} = bf16[1,32,16384,128] custom-call(bf16[1,32,16384,128] %p), custom_call_target=tpu_custom_call"
    win = (0.040, 0.030, 0.045) if masked else (0.010, 0.008, 0.012)
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 0.5))
        for layer in range(6):
            t = at + 0.50 + 0.12 * layer
            ops += [
                (call.format(f"flash_win_fwd.{2 + layer}"), t, win[0]), (call.format(f"flash_win_dq.{2 + layer}"), t + 0.04, win[1]),
                (call.format(f"flash_win_dkv.{2 + layer}"), t + 0.07, win[2]),
            ]
        for layer in range(2):
            t = at + 1.25 + 0.12 * layer
            ops += [
                (call.format(f"flash_fwd.{2 + layer}"), t, 0.040), (call.format(f"flash_dq.{2 + layer}"), t + 0.04, 0.030),
                (call.format(f"flash_dkv.{2 + layer}"), t + 0.07, 0.045),
            ]
        ops += [
            (call.format("jvp_jit_gmm__.4"), at + 1.50, 0.150),
            (call.format("transpose_jvp_jit_tgmm___.9"), at + 1.65, 0.090),
            ("%copy.8 = bf16[1,32,16384,128] copy(%flash_win_fwd.2)", at + 1.75, 0.001),
            ("%copy.9 = bf16[1,32,16384,128] copy(%flash_fwd.2)", at + 1.751, 0.001),
        ]
    event = lambda t, rows: dict(  # noqa: E731
        name="MOE_ROUTE", t=t, rows_here=[rows] * 7, load_max=[1.5 * rows / 16] * 7, load_mean=[rows / 16] * 7
    )
    return _trace_sources(cell, ops, [event(2.9, 16384.0), event(4.9, 17408.0), event(0.5, 9.0)])


# ``swa_full_flash_roofline``, ``swa_moe_gmm_roofline`` and ``swa_step_mfu_pct`` were three more until PR 66: the
# cell is on the three folded readers' lists (``flash_roofline`` reads the FULL layers' kernels)
NEW_READERS = ("swa_flash_ms", "swa_flash_roofline", "swa_window_over_full_pct")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "moe_gmm_roofline", "quorum_ms", "commit_vote_ms", "step_device_ms", "device_idle_pct", "peak_hbm_gb",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean",
          "moe_route_ms", "moe_dispatch_ms", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms", "xla_stream_ms",
          "xla_head_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    # the windowed layers' kernels and the full layers' are told apart by name, in both directions
    assert read("swa_flash_ms") == pytest.approx(6 * 30.0)
    assert read("flash_fwd_ms") == pytest.approx(80.0) and read("flash_dq_ms") == pytest.approx(60.0)
    assert read("flash_dkv_ms") == pytest.approx(90.0) and read("moe_gmm_ms") == pytest.approx(240.0)
    assert read("swa_window_over_full_pct") == pytest.approx(100 * 30.0 / 115.0)
    count, s = cell.architecture.swa_flops, sources["shapes"]
    for name, need, seconds in (
        ("swa_flash_roofline", count.win_flash_step(s, 1, SEQ), 0.180),
        ("flash_roofline", count.full_flash_step(s, 1, SEQ), 0.230),
        ("moe_gmm_roofline", count.gmm_step(s, 16896.0), 0.240),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    # at 16,384 positions both kinds of layer are bound by compute
    assert flops.roofline_pct(*count.win_flash_step(s, 1, SEQ), 1.0, "TPU v5 lite")["bound"] == "compute"
    assert flops.roofline_pct(*count.full_flash_step(s, 1, SEQ), 1.0, "TPU v5 lite")["bound"] == "compute"
    assert read("moe_rows_here_per_step") == pytest.approx(7 * 16896.0)
    assert read("moe_load_max_over_mean") == pytest.approx(1.5)
    busy = 0.5 + 0.180 + 0.230 + 0.240 + 0.002  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    # the readers of another architecture's shapes find nothing here
    for theirs in ("kda_roofline", "dsa_attn_roofline", "ssd_roofline", "gdn_roofline", "eva_flash_roofline"):
        assert read(theirs) is None, theirs


def test_a_window_that_masks_a_full_walk_reads_a_hundred(cell):
    """What ``swa_window_over_full_pct`` is for: kernels that took a full
    layer's time on a windowed layer read 100, and their share of the
    roofline falls by the same factor, since only the live pairs are credited."""
    read = lambda name, sources: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    skipping, masking = _made_trace(cell), _made_trace(cell, masked=True)
    assert read("swa_window_over_full_pct", masking) == pytest.approx(100.0)
    assert read("swa_window_over_full_pct", skipping) < 40 < read("swa_window_over_full_pct", masking)
    assert read("swa_flash_roofline", skipping) / read("swa_flash_roofline", masking) == pytest.approx(115.0 / 30.0)
    # a cell with no full layer, or none windowed, has no ratio
    for missing in ("n_full", "n_windowed"):
        sources = _made_trace(cell)
        sources["shapes"] = dict(sources["shapes"], **{missing: 0})
        assert read("swa_window_over_full_pct", sources) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("lower" if name in ("swa_flash_ms", "swa_window_over_full_pct") else "higher")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such kernel and no such architecture: the
    reader returns None, never raises, and the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "keye2-ws1-seq16k", "nemotron3nano-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        read = spec.load_metric(name, BENCH_DIR).read
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
    # this architecture's shapes over a trace without its kernels: still nothing for a kernel's reader
    assert spec.load_metric(name, BENCH_DIR).read(_trace_sources(cell, ops, old_events)) is None
    # a full layer's kernels alone (a window that covers the sequence) are not the windowed ones
    if name in ("swa_flash_ms", "swa_flash_roofline", "swa_window_over_full_pct"):
        full_only = [
            ("%flash_fwd.2 = bf16[1,32,16384,128] custom-call(%p), custom_call_target=tpu_custom_call", at, 0.04)
            for at in (1.5, 3.5)
        ]
        assert spec.load_metric(name, BENCH_DIR).read(_trace_sources(cell, full_only, old_events)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="trinity-mini-ep8-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "1,024 tokens" in entry["why"] and "frozen" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    # what this model has no part of stays without it: another architecture's
    # kernels, another regime's end-to-end metric.  Named by what they are, not
    # by a closed list of today's readers: a later PR's reader may list the cell
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_")), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths, the window SHORTER than
    the sequence: Manager, ``HSDPTrainer``, the selection bias in its slot, the
    step's summary in the flight events, the float32 reference with its
    explicit mask, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000047", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"swa_flash_ms", "swa_flash_roofline", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.4459, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["config"]["sliding_window"] < toy["seq_len"]
