"""The configuration ``qwen3-next-80b-a3b-ep16-1x1``, its architecture file, its
counting of operations and bytes, its readers and the CPU rehearsal of the
cell ``qwen3next-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "qwen3next-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts_per_tok",
    "partial_rotary_factor",
)
SEQ = 16384


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"])
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["moe_intermediate_size"], config["shared_expert_intermediate_size"]) == (2048, 512, 512)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (16, 2, 256)
    assert (config["partial_rotary_factor"], config["rope_theta"], config["rms_norm_eps"]) == (0.25, 10000000, 1e-6)
    assert (config["linear_num_key_heads"], config["linear_num_value_heads"]) == (16, 32)
    assert (config["linear_key_head_dim"], config["linear_value_head_dim"], config["linear_conv_kernel_dim"]) == (128, 128, 4)
    assert (config["num_experts_per_tok"], config["norm_topk_prob"], config["tie_word_embeddings"]) == (10, True, False)
    assert (config["full_attention_interval"], config["decoder_sparse_step"], config["mlp_only_layers"]) == (4, 1, [])
    # two whole periods: six Gated DeltaNet layers to two full, three to one as published
    assert config["num_hidden_layers"] == 8 and config["published"]["num_hidden_layers"] == 48
    assert cell.architecture.reference.layer_kinds(config) == ["gdn", "gdn", "gdn", "full"] * 2
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == config["published"]["num_experts"] == 512
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 32]
    # the floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("learning_rate", "optimizer", "balance_loss_weight", "balance_loss", "decay_init_max", "dt_bias_init",
                "decay_init", "layouts", "convolution", "norms", "rope", "gated_attention", "router",
                "residual_stream", "mtp", "chunk", "weights", "batch", "kernels", "model_code"):
        assert key in config["assumed"], key
    assumed = config["assumed"]
    assert (assumed["learning_rate"], assumed["balance_loss_weight"]) == (1e-6, 1e-3)
    # the initial values that decide how far below -5 a decay goes are the published code's, not near 0
    assert (assumed["decay_init_max"], assumed["dt_bias_init"]) == (16.0, 1.0)
    assert assumed["mtp"].startswith("NO multi-token-prediction module")
    assert "16 chips share" in config["stands_for"] and "320 tokens" in config["stands_for"] and "5,120" in config["stands_for"]
    assert config["parameters_here"].startswith("1,173,540,992 (1,173.5 M")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 1_173_540_992
    assert arch.vocab(config) == 18_992 and arch.KERNEL_PATHS == {"gdn+flash"}
    s = arch.shapes(config)
    assert (s["n_gdn"], s["n_full"], s["key_heads"], s["value_heads"], s["head_dim"], s["rotary_dim"]) == (6, 2, 16, 32, 256, 64)
    count = arch.gdn_flops
    other = spec.load_cell("ling3flash-ws1-seq8k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config))
    # ISSUE 56: a token's matmul parameters: a DeltaNet mixer 33.69 M (W_qkvz, W_ba, W_o), a full one 27.26 M, the
    # expert block router 1.05 M + shared 3.15 M + its gate + 10 x 32/512 x 3.15 M routed, the head 38.90 M
    delta_net = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 32 / 512 * 3 * 2048 * 512
    assert count.matmul_params_touched(s) == 6 * delta_net + 2 * full + 8 * moe + 2048 * 18992
    assert (delta_net, full, moe) == (33_685_504, 27_262_976, 6_162_432)
    # the recurrence: 6 dk dv a token and VALUE head forward, twice that backward
    operations, nbytes = count.gdn_step(s, 1, SEQ)
    assert operations == 6 * 3 * 6 * 128 * 128 * SEQ * 32
    assert nbytes == 6 * 2 * SEQ * ((2 * 16 * 128 + 2 * 32 * 128) * 2 + 2 * 32 * 4)
    # a layer's recurrence is 0.79 ms of operations and 0.98 ms of bytes: the bound is memory, as KDA's
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "memory"
    # full attention at heads of 256: the live causal pairs
    operations, _ = count.flash_step(s, 1, SEQ)
    assert operations == 2 * 6 * 2 * (SEQ * (SEQ + 1) / 2) * 256 * 16


def test_the_yardsticks_k_lies_between_its_two_readings_with_room_on_both_sides(cell):
    """The chip's readings (the architecture file says where they are kept):
    the worst sound seed and the nearest int8 control each stay half again
    away from ``K``."""
    arch = cell.architecture
    assert arch.READ_SOUND_LOW <= arch.READ_SOUND_HIGH
    assert 1.5 * arch.READ_CONTROL_HIGH < arch.COARSE_RATIO_K < arch.READ_SOUND_LOW / 1.5


def test_counting_by_hand_at_toy_widths(cell):
    """``gdn_flops`` against a count by hand: 2 key heads of 4 under 4 value
    heads of 8, 4 query heads of 8 over 2, 8 positions; two DeltaNet layers and
    one full."""
    count = cell.architecture.gdn_flops
    s = dict(dim=16, n_gdn=2, n_full=1, n_heads=4, n_kv_heads=2, head_dim=8, key_heads=2, value_heads=4,
             key_head_dim=4, value_head_dim=8, expert_hidden=12, shared_hidden=12, router_experts=8, experts_held=2,
             top_k=2, vocab_size=32)
    operations, nbytes = count.gdn_step(s, rows=1.0, seq=8)
    assert operations == 2 * (3 * 6 * 4 * 8 * 8 * 4)  # 6 dk dv a token and value head, three times, two layers
    # q, k (2 heads of 4), v, o (4 of 8) in bfloat16, g and beta (4) in float32, each way
    assert nbytes == 2 * 2 * (8 * (2 * 2 * 4 + 2 * 4 * 8) * 2 + 8 * 2 * 4 * 4)
    operations, nbytes = count.flash_step(s, rows=1.0, seq=8)
    assert operations == 6 * 2 * 36 * 8 * 4  # six products of 2 D a LIVE pair a query head: 8 x 9 / 2 pairs
    assert nbytes == 8 * 8 * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) * 2
    operations, nbytes = count.gmm_step(s, rows_here=10.0)
    assert operations == 3 * (9 * 2 * 16 * 12 * 10)  # every layer has experts
    assert nbytes == 3 * (3 * 2 * (3 * 16 * 12) * 2 + 3 * 10 * (3 * 16 + 3 * 12) * 2)
    delta_net = 16 * (2 * 8 + 2 * 32) + 16 * 8 + 32 * 16
    full = 16 * 2 * 32 + 2 * 16 * 16 + 32 * 16
    moe = 16 * 8 + 3 * 16 * 12 + 16 + 2 * 2 / 8 * 3 * 16 * 12
    params = 2 * delta_net + full + 3 * moe + 16 * 32
    assert count.matmul_params_touched(s) == params
    both = count.gdn_step(s, 1.0, 8)[0] + count.flash_step(s, 1.0, 8)[0]
    assert count.train_flops_per_token(s, 8) == 6 * params + both / 8


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell):
    """Two steps as the chip's trace names them: six layers' ``gdn_fwd``
    (twice: 12 ms each) and ``gdn_bwd`` (30 ms), two layers' ``flash_*`` (40, 30
    and 45 ms), the grouped products, and operations that only MENTION a
    kernel."""
    call = "%{} = bf16[1,32,16384,128] custom-call(bf16[1,32,16384,128] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 0.5))
        for layer in range(6):
            t = at + 0.50 + 0.10 * layer
            ops += [
                (call.format(f"gdn_fwd.{2 + layer}"), t, 0.012), (call.format(f"gdn_fwd.{12 + layer}"), t + 0.02, 0.012),
                (call.format(f"gdn_bwd.{2 + layer}"), t + 0.04, 0.030),
            ]
        for layer in range(2):
            t = at + 1.15 + 0.12 * layer
            ops += [
                (call.format(f"flash_fwd.{2 + layer}"), t, 0.040), (call.format(f"flash_dq.{2 + layer}"), t + 0.04, 0.030),
                (call.format(f"flash_dkv.{2 + layer}"), t + 0.07, 0.045),
            ]
        ops += [
            (call.format("jvp_jit_gmm__.4"), at + 1.50, 0.150),
            (call.format("transpose_jvp_jit_tgmm___.9"), at + 1.65, 0.090),
            ("%copy.8 = bf16[1,32,16384,128] copy(%gdn_fwd.2)", at + 1.75, 0.001),
            ("%copy.9 = bf16[1,16,16384,256] copy(%flash_fwd.2)", at + 1.751, 0.001),
        ]
    event = lambda t, rows, low: dict(  # noqa: E731
        name="MOE_ROUTE", t=t, rows_here=[rows] * 8, load_max=[1.5 * rows / 32] * 8, load_mean=[rows / 32] * 8,
        buffer_rows=[12800.0] * 8, decay_min=[low, low / 2, low / 3, 0.0] * 2,
    )
    return _trace_sources(cell, ops, [event(2.9, 10240.0, -41.0), event(4.9, 10752.0, -44.5), event(0.5, 9.0, -99.0)])


# ``gdn_flash_roofline`` and ``gdn_step_mfu_pct`` were two more until PR 66: the cell is on the lists of
# ``flash_roofline`` and ``step_mfu_pct``, and on ``moe_gmm_roofline``'s, which it never had a fork of
NEW_READERS = ("gdn_fwd_ms", "gdn_roofline", "gdn_decay_min")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "moe_gmm_roofline", "quorum_ms", "commit_vote_ms", "step_device_ms", "device_idle_pct", "peak_hbm_gb",
          "moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean", "moe_route_ms", "moe_dispatch_ms",
          "moe_buffer_fill_pct", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms",
          "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("gdn_fwd_ms") == pytest.approx(6 * 24.0)  # both runs of the forward kernel, not the backward's
    assert read("moe_gmm_ms") == pytest.approx(240.0)
    count, s = cell.architecture.gdn_flops, sources["shapes"]
    for name, need, seconds in (
        ("gdn_roofline", count.gdn_step(s, 1, SEQ), 6 * 0.054),
        ("flash_roofline", count.flash_step(s, 1, SEQ), 0.230),
        ("moe_gmm_roofline", count.gmm_step(s, 10496.0), 0.240),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    assert read("moe_rows_here_per_step") == pytest.approx(8 * 10496.0)
    assert read("moe_load_max_over_mean") == pytest.approx(1.5)
    assert read("moe_buffer_fill_pct") == pytest.approx(100 * 10496.0 / 12800.0)
    # the most negative decay of the window's events (the one before the window is not read)
    assert read("gdn_decay_min") == -44.5
    busy = 0.5 + 6 * 0.054 + 0.230 + 0.240 + 0.002  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    # the readers of another architecture's shapes find nothing here
    for theirs in ("kda_roofline", "ssd_roofline", "swa_flash_roofline", "dsa_attn_roofline", "eva_flash_roofline"):
        assert read(theirs) is None, theirs


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("lower" if name in ("gdn_fwd_ms", "gdn_decay_min") else "higher")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such kernel, no such architecture and no
    ``decay_min`` in its events: the reader returns None, never raises, and
    the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "keye2-ws1-seq16k", "trinitymini-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        read = spec.load_metric(name, BENCH_DIR).read
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
    # this architecture's shapes over a trace without its kernels: still nothing for a kernel's reader
    assert spec.load_metric(name, BENCH_DIR).read(_trace_sources(cell, ops, old_events)) is None
    # Ling's flash and KDA kernels under another architecture's shapes are not this one's
    if name in ("gdn_fwd_ms", "gdn_roofline"):
        ling = spec.load_cell("ling3flash-ws1-seq8k")
        theirs = [
            (f"%{kernel}.2 = bf16[1,32,8192,128] custom-call(%p), custom_call_target=tpu_custom_call", at, 0.04)
            for at in (1.5, 3.5) for kernel in ("kda_fwd", "kda_bwd", "flash_fwd")
        ]
        sources = dict(_trace_sources(cell, theirs, old_events), shapes=ling.architecture.shapes(ling.config))
        assert spec.load_metric(name, BENCH_DIR).read(sources) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="qwen3-next-80b-a3b-ep16-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "320 tokens" in entry["why"] and "frozen" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    # the contract's cap on per-layer metrics (two of the three readers ISSUE 56 named and left out stand since PR 66)
    assert len(bench["per_layer"]) <= 128
    # what this model has no part of stays without it, named by what it is
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_", "eva_", "latent_")), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean", "moe_buffer_fill_pct",
             "gdn_decay_min"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary in the flight events with its ``decay_min``, the float32
    reference with its token-by-token recurrence, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000047", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"gdn_fwd_ms", "gdn_roofline", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.4913, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["config"]["linear_num_value_heads"] == 2 * toy["config"]["linear_num_key_heads"]
