"""The configuration ``smallthinker-21b-a3b-ep2-1x1``, its architecture file,
its counting of operations and bytes, its readers and the CPU rehearsal of the
cell ``smallthinker-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "smallthinker-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "moe_ffn_hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_num_active_primary_experts", "sliding_window_size",
)
REDUCED = ["moe_num_primary_experts", "num_hidden_layers", "rope_layout", "sliding_window_layout", "vocab_size"]
SEQ = 16384


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"]) == REDUCED
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["moe_ffn_hidden_size"], config["moe_num_active_primary_experts"]) == (2560, 768, 6)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (28, 4, 128)
    assert (config["sliding_window_size"], config["rope_theta"], config["rms_norm_eps"]) == (4096, 1500000, 1e-6)
    assert (config["moe_primary_router_apply_softmax"], config["norm_topk_prob"], config["tie_word_embeddings"]) == (True, True, False)
    assert config["max_position_embeddings"] == SEQ  # the cell runs the model's whole published context
    # the two lists are the published ones' first period: the NoPE-global layer FIRST, then three windowed with rope
    published = config["published"]
    assert published["rope_layout"] == published["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["rope_layout"] == config["sliding_window_layout"] == [0, 1, 1, 1]
    assert config["num_hidden_layers"] == 4 and published["num_hidden_layers"] == 52  # four expert layers: the floor, met exactly
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == published["moe_num_primary_experts"] == 64
    assert config["experts_held"] == [0, config["moe_num_primary_experts"]] == [0, 32]
    # the floors: 8 experts, an eighth of the vocabulary
    assert config["moe_num_primary_experts"] >= 8 and config["vocab_size"] * 8 == published["vocab_size"] == 151936
    for key in ("learning_rate", "optimizer", "router_input", "router", "selection_bias", "balance_loss_weight", "experts",
                "attention", "rope", "window", "norms", "residual_stream", "weights", "batch", "kernels", "model_code"):
        assert key in config["assumed"], key
    assert config["assumed"]["router_input"].startswith("(smallthinker)")  # what only the family's code gives
    assert config["assumed"]["learning_rate"] == 1e-6 and config["assumed"]["balance_loss_weight"] == 0.0
    assert "TWO chips share" in config["stands_for"] and "8 chips share the vocabulary" in config["stands_for"]
    assert "1,536 tokens" in config["stands_for"] and "3,072" in config["stands_for"]
    assert config["parameters_here"].startswith("936.78 M")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 936_778_240
    assert arch.vocab(config) == 18_992 and arch.KERNEL_PATHS == {"flash_win+flash"}
    s = arch.shapes(config)
    assert (s["n_prerouted_windowed"], s["n_prerouted_global"], s["window"], s["n_heads"], s["n_kv_heads"]) == (3, 1, 4096, 28, 4)
    count = arch.flops
    assert count is arch.prerouted_flops
    # its own shapes and no other's: Trinity's are the nearest (a window, experts) and are not
    trinity = spec.load_cell("trinitymini-ws1-seq16k")
    theirs = trinity.architecture.shapes(trinity.config)
    assert count.is_mine(s) and not count.is_mine(theirs) and not count.is_mine(None)
    assert not trinity.architecture.flops.is_mine(s)
    # ISSUE 67: a token's matmul parameters: attention 20.97 M a layer (q, o 9.18 M each; k, v 1.31 M each), the
    # router 0.16 M, 6 x 32 / 64 = 3 experts of 5.90 M, the head 48.62 M
    attention, router, expert, head = 2 * 2560 * 3584 + 2 * 2560 * 512, 2560 * 64, 3 * 2560 * 768, 2560 * 18992
    assert count.matmul_params_touched(s) == 4 * (attention + router + 3 * expert) + head
    assert (attention, router, expert, head) == (20_971_520, 163_840, 5_898_240, 48_619_520)
    # the live pairs: a window of 4,096 at 16,384 positions leaves 44 % of the causal pairs
    assert count.live_pairs(SEQ, 4096) == SEQ * 4096 - 4096 * 4095 / 2
    assert count.live_pairs(SEQ, None) == count.live_pairs(SEQ, SEQ) == count.live_pairs(SEQ, 10 * SEQ) == SEQ * (SEQ + 1) / 2
    assert count.live_pairs(SEQ, 4096) / count.live_pairs(SEQ) == pytest.approx(0.4375, abs=1e-4)
    assert count.live_pairs(8, 1) == 8 and count.live_pairs(8, 3) == 1 + 2 + 6 * 3
    # ISSUE 67's shares of a step's FORWARD operations: the routed experts 2.32 T of 11.1 T
    forward = count.train_flops_per_token(s, SEQ) * SEQ / 3
    assert forward == pytest.approx(11.13e12, rel=1e-3) and 2 * SEQ * 4 * 3 * expert / forward == pytest.approx(0.208, abs=1e-3)


def test_counting_by_hand_at_toy_widths(cell):
    """``prerouted_flops`` against a count by hand: 4 heads of 8 over 2, a
    window of 3 over 8 positions; two windowed layers and one global, all of
    experts."""
    count = cell.architecture.flops
    s = dict(dim=16, n_prerouted_windowed=2, n_prerouted_global=1, window=3, n_heads=4, n_kv_heads=2, head_dim=8,
             expert_hidden=12, router_experts=8, experts_held=2, top_k=2, vocab_size=32)
    # attention: six products of 2 D a LIVE pair a query head; a window of 3 over 8 rows: 1 + 2 + 6 x 3 = 21 pairs
    operations, nbytes = count.win_flash_step(s, rows=1.0, seq=8)
    assert operations == 2 * (6 * 2 * 21 * 8 * 4)
    moved = 8 * 8 * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) * 2  # q, o (4 heads) and k, v (2) of 8, forward and backward
    assert nbytes == 2 * moved
    # the global layer: the causal half, 8 x 9 / 2 = 36 pairs; the same operands moved
    operations, nbytes = count.flash_step(s, rows=1.0, seq=8)
    assert operations == 6 * 2 * 36 * 8 * 4 and nbytes == moved
    # experts: THREE products forward and six backward a row, in all three layers; three matrices an expert
    operations, nbytes = count.gmm_step(s, rows_here=10.0)
    assert operations == 3 * (9 * 2 * 16 * 12 * 10)
    assert nbytes == 3 * (3 * 2 * (3 * 16 * 12) * 2 + 3 * 10 * (3 * 16 + 3 * 12) * 2)
    attention = 2 * 16 * 32 + 2 * 16 * 16
    params = 3 * (attention + 16 * 8 + 2 * 2 / 8 * 3 * 16 * 12) + 16 * 32
    assert count.matmul_params_touched(s) == params
    both = count.win_flash_step(s, 1.0, 8)[0] + count.flash_step(s, 1.0, 8)[0]
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
    """Two steps as the chip's trace names them: three layers' ``flash_win_*``
    (the forward twice: 2 x 10, 16 and 24 ms a layer; ``masked``: as long as
    the global layer's), one layer's ``flash_*`` (20, 30 and 45 ms), the
    grouped products, and operations that only MENTION a kernel."""
    call = "%{} = bf16[1,28,16384,128] custom-call(bf16[1,28,16384,128] %p), custom_call_target=tpu_custom_call"
    win = (0.0475, 0.0475) if masked else (0.016, 0.024)
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[16384,2560] fusion(%p)", at, 0.4))
        for layer in range(3):
            t = at + 0.40 + 0.12 * layer
            ops += [
                (call.format(f"flash_win_fwd.{2 + layer}"), t, 0.010), (call.format(f"flash_win_fwd.{5 + layer}"), t + 0.02, 0.010),
                (call.format(f"flash_win_dq.{2 + layer}"), t + 0.04, win[0]), (call.format(f"flash_win_dkv.{2 + layer}"), t + 0.07, win[1]),
            ]
        t = at + 0.80
        ops += [
            (call.format("flash_fwd.2"), t, 0.020), (call.format("flash_dq.2"), t + 0.04, 0.030), (call.format("flash_dkv.2"), t + 0.08, 0.045),
            (call.format("jvp_jit_gmm__.4"), at + 1.00, 0.045),
            (call.format("transpose_jvp_jit_tgmm___.9"), at + 1.10, 0.025),
            ("%copy.8 = bf16[1,28,16384,128] copy(%flash_win_fwd.2)", at + 1.20, 0.001),
            ("%copy.9 = bf16[1,28,16384,128] copy(%flash_fwd.2)", at + 1.201, 0.001),
        ]
    event = lambda t, rows: dict(  # noqa: E731
        name="MOE_ROUTE", t=t, rows_here=[rows] * 4, load_max=[1.25 * rows / 32] * 4, load_mean=[rows / 32] * 4,
        buffer_rows=[61440.0] * 4,
    )
    return _trace_sources(cell, ops, [event(2.9, 49152.0), event(4.9, 50176.0), event(0.5, 9.0)])


NEW_READERS = ("prerouted_win_flash_ms", "prerouted_win_flash_roofline", "prerouted_window_over_full_pct")
JOINED = ("tokens_per_s_per_chip", "quorum_ms", "commit_vote_ms", "step_device_ms", "step_mfu_pct", "device_idle_pct",
          "peak_hbm_gb", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms",
          "optimizer_ms", "step_remat_ms", "xla_unscoped_ms", "flash_roofline", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
          "moe_gmm_ms", "moe_gmm_roofline", "moe_rows_here_per_step", "moe_load_max_over_mean", "moe_route_ms",
          "moe_dispatch_ms", "moe_buffer_fill_pct")


def test_kernel_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    # the windowed layers' kernels and the global layer's are told apart by name, in both directions
    assert read("prerouted_win_flash_ms") == pytest.approx(60.0)  # a step AND windowed layer
    assert read("flash_fwd_ms") == pytest.approx(20.0) and read("flash_dq_ms") == pytest.approx(30.0)
    assert read("flash_dkv_ms") == pytest.approx(45.0) and read("moe_gmm_ms") == pytest.approx(70.0)
    assert read("prerouted_window_over_full_pct") == pytest.approx(100 * 60.0 / 95.0)
    count, s = cell.architecture.flops, sources["shapes"]
    for name, need, seconds in (
        ("prerouted_win_flash_roofline", count.win_flash_step(s, 1, SEQ), 0.180),
        ("flash_roofline", count.flash_step(s, 1, SEQ), 0.095),
        ("moe_gmm_roofline", count.gmm_step(s, 49664.0), 0.070),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    # at 16,384 positions both kinds of layer, and the grouped products at 1,536 rows an expert, are bound by compute
    for need in (count.win_flash_step(s, 1, SEQ), count.flash_step(s, 1, SEQ), count.gmm_step(s, 49152.0)):
        assert flops.roofline_pct(*need, 1.0, "TPU v5 lite")["bound"] == "compute"
    assert read("moe_rows_here_per_step") == pytest.approx(4 * 49664.0)
    assert read("moe_load_max_over_mean") == pytest.approx(1.25)
    assert read("moe_buffer_fill_pct") == pytest.approx(100 * 49664.0 / 61440.0)
    busy = 0.4 + 0.180 + 0.095 + 0.070 + 0.002  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    # the readers of another architecture's shapes find nothing here, Trinity's windowed shares among them
    # (``swa_flash_ms`` goes by the kernels' names alone, and does not list this cell)
    for theirs in ("kda_roofline", "dsa_attn_roofline", "ssd_roofline", "gdn_roofline", "eva_flash_roofline",
                   "swa_flash_roofline", "swa_window_over_full_pct", "sambay_flash_roofline"):
        assert read(theirs) is None, theirs


def test_a_window_that_masks_a_full_walk_reads_a_hundred_and_over(cell):
    """What ``prerouted_window_over_full_pct`` is for: kernels that took the
    global layer's time on a windowed layer read 100 and over (the windowed
    layer runs its forward twice), and their share of the roofline falls by
    the same factor, since only the live pairs are credited."""
    read = lambda name, sources: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    skipping, masking = _made_trace(cell), _made_trace(cell, masked=True)
    assert read("prerouted_window_over_full_pct", masking) == pytest.approx(100 * 115.0 / 95.0)
    assert read("prerouted_window_over_full_pct", skipping) < 70 < 100 < read("prerouted_window_over_full_pct", masking)
    ratio = read("prerouted_win_flash_roofline", skipping) / read("prerouted_win_flash_roofline", masking)
    assert ratio == pytest.approx(115.0 / 60.0)
    # a cell with no global layer, or none windowed, has no ratio
    for missing in ("n_prerouted_global", "n_prerouted_windowed"):
        sources = _made_trace(cell)
        sources["shapes"] = dict(sources["shapes"], **{missing: 0})
        assert read("prerouted_window_over_full_pct", sources) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip" and entry["layer"] == "kernels"
    assert entry["better"] == ("higher" if name == "prerouted_win_flash_roofline" else "lower")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such architecture: the reader returns None,
    never raises, and the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    win_calls = [
        (f"%flash_win_{k}.2 = bf16[1,32,16384,128] custom-call(%p), custom_call_target=tpu_custom_call", at, 0.04)
        for at in (1.5, 3.5) for k in ("fwd", "dq", "dkv")
    ]
    read = spec.load_metric(name, BENCH_DIR).read
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "trinitymini-ws1-seq16k", "phi4miniflash-ws1-seq16k"):
        theirs = spec.load_cell(other)
        # another architecture's shapes, even over a trace that HAS windowed kernels (Trinity's, Phi-4's)
        sources = dict(_trace_sources(cell, ops + win_calls), architecture=theirs.architecture,
                       shapes=theirs.architecture.shapes(theirs.config))
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
    # this architecture's shapes over a trace without its kernels: still nothing
    assert read(_trace_sources(cell, ops)) is None and read(dict(_trace_sources(cell, ops), trace=None)) is None
    # the global layer's kernels alone are not the windowed ones
    full_only = [
        ("%flash_fwd.2 = bf16[1,28,16384,128] custom-call(%p), custom_call_target=tpu_custom_call", at, 0.04)
        for at in (1.5, 3.5)
    ]
    assert read(_trace_sources(cell, full_only)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="smallthinker-21b-a3b-ep2-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "1,536 tokens" in entry["why"] and "more than its share" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    # what this model has no part of stays without it: a dense MLP or a shared expert (``tpuft.ffn``), Trinity's
    # windowed readers, another architecture's kernels, another regime's end-to-end metric.  Named by what they
    # are, not by a closed list of today's readers: a later PR's reader may list the cell
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert name != "xla_ffn_ms" and not name.startswith(("swa_", "kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "gdn_", "eva_", "selscan_", "sambay_", "loop_")), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean", "moe_buffer_fill_pct"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths, the window SHORTER than
    the sequence and seven query heads to a key head: Manager, ``HSDPTrainer``,
    the step's summary in the flight events, the float32 reference with its
    explicit mask and its router on the layer's input, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000047", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {*NEW_READERS, "step_device_ms", "xla_ffn_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.3324, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["config"]["sliding_window_size"] < toy["seq_len"]
    assert toy["config"]["num_attention_heads"] == 7 * toy["config"]["num_key_value_heads"]
