"""The configuration ``joyai-llm-flash-ep16-1x1``, its architecture file, its
counting of operations and bytes, its readers and the CPU rehearsal of the
cell ``joyaiflash-ws1-seq16k``.  No number here is a device's."""

import json
import math
import os

import pytest

from ftbench import device_scopes, flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "joyaiflash-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "q_lora_rank", "kv_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "num_experts_per_tok", "n_shared_experts", "n_group", "topk_group",
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
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]) == (2048, 7168, 768)
    assert (config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"]) == (32, 1536, 512)
    assert (config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["qk_head_dim"], config["v_head_dim"]) == (128, 64, 192, 128)
    assert (config["rope_theta"], config["rope_interleave"], config["rope_scaling"], config["rms_norm_eps"]) == (32_000_000, True, None, 1e-6)
    assert (config["num_experts_per_tok"], config["n_group"], config["topk_group"], config["scoring_func"]) == (8, 1, 1, "sigmoid")
    assert (config["routed_scaling_factor"], config["norm_topk_prob"], config["topk_method"]) == (2.5, True, "noaux_tc")
    # the module stays: this chip stands for the first stage and, with the head it holds, the last
    assert config["num_nextn_predict_layers"] == 1 and "num_nextn_predict_layers" not in config["reduced"]
    # layer 0 dense and SIX expert layers: the period is one layer, the floor is four
    assert (config["num_hidden_layers"], config["first_k_dense_replace"], config["moe_layer_freq"]) == (7, 1, 1)
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == config["published"]["n_routed_experts"] == 256
    assert config["experts_held"] == [0, config["n_routed_experts"]] == [0, 16]
    # the floors: 8 experts, an eighth of the vocabulary
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("learning_rate", "optimizer", "bias_update_rate", "bias_update", "balance_loss_weight", "balance_loss",
                "mtp_loss_weight", "mtp", "latent_norms", "attention_scale", "rope", "router", "residual_stream",
                "weights", "kernels", "remat"):
        assert key in config["assumed"], key
    assert (config["assumed"]["learning_rate"], config["assumed"]["mtp_loss_weight"]) == (1e-6, 0.1)
    assert (config["assumed"]["bias_update_rate"], config["assumed"]["balance_loss_weight"]) == (0.001, 0.0001)
    assert "16 chips share" in config["stands_for"] and "512 tokens" in config["stands_for"] and "8,192" in config["stands_for"]
    assert config["parameters_here"].startswith("894.63 M")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 894_625_536
    assert arch.vocab(config) == 16_160 and arch.KERNEL_PATHS == {"flash"}
    s = arch.shapes(config)
    assert (s["n_dense"], s["n_moe"], s["n_mtp"], s["qk_head_dim"], s["v_head_dim"]) == (1, 6, 1, 192, 128)
    count = arch.latent_flops
    other = spec.load_cell("ling3flash-ws1-seq8k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config)) and not count.is_mine(None)
    assert (count.attention_layers(s), count.expert_layers(s)) == (8, 7)  # the module's layer counted
    # ISSUE 49: attention 26.35 M a layer (3.15 + 9.44 + 1.18 + 4.19 + 8.39), the dense SwiGLU 44.04 M, an expert
    # layer 4.72 M shared + 0.52 M router + 8 x 4.72 M x 16/256 routed, the head 33.1 M TWICE, the pair's projection 8.39 M
    attention = 2048 * 1536 + 1536 * 32 * 192 + 2048 * (512 + 64) + 512 * 32 * (128 + 128) + 32 * 128 * 2048
    dense, head, pair = 3 * 2048 * 7168, 2048 * 16160, 2 * 2048 * 2048
    moe = 2048 * 256 + 3 * 2048 * 768 + 8 * 16 / 256 * 3 * 2048 * 768
    assert count.attention_params(s) == attention == 26_345_472
    assert count.matmul_params_touched(s) == 8 * attention + dense + 7 * moe + 2 * head + pair == 382_599_168
    assert (dense, head, pair, moe) == (44_040_192, 33_095_680, 8_388_608, 7_602_176)
    # the causal attention of a step: 8 layers x 32 heads x (3 x 192 + 3 x 128) x 2 x S x S / 2
    operations, nbytes = count.flash_step(s, 1.0, SEQ)
    assert operations == 8 * 32 * 960 * SEQ * SEQ and operations == pytest.approx(6.597e13, rel=1e-3)
    assert nbytes == 8 * SEQ * 32 * (6 * 192 + 6 * 128) * 2
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "compute"
    # of the step's operations latent attention is 64 %: the cell's "two thirds"
    per_token = count.train_flops_per_token(s, SEQ)
    assert per_token == 6 * 382_599_168 + operations / SEQ
    assert operations / SEQ / per_token == pytest.approx(0.637, abs=2e-3)
    # a layer's attention against a layer's products (ISSUE 49: 74 % of a layer's)
    layer_matmul = 6 * (attention + moe)
    assert (operations / 8 / SEQ) / (operations / 8 / SEQ + layer_matmul) == pytest.approx(0.712, abs=5e-3)


def test_counting_by_hand_at_toy_widths(cell):
    """``latent_flops`` against a count by hand: 2 heads of 6 + 2 for q and k
    and 4 for v, latents of 5 and 3; one dense layer, two of experts, the module."""
    count = cell.architecture.latent_flops
    s = dict(dim=16, n_dense=1, n_moe=2, n_mtp=1, n_heads=2, q_lora_rank=5, kv_lora_rank=3, qk_head_dim=8,
             qk_rope_head_dim=2, v_head_dim=4, dense_hidden=24, expert_hidden=12, shared_hidden=12, router_experts=8,
             experts_held=2, top_k=2, vocab_size=32)
    attention = 16 * 5 + 5 * 2 * 8 + 16 * (3 + 2) + 3 * 2 * (6 + 4) + 2 * 4 * 16
    assert count.attention_params(s) == attention == 428
    moe = 16 * 8 + 3 * 16 * 12 + 2 * 2 / 8 * 3 * 16 * 12
    params = 4 * attention + 3 * 16 * 24 + 3 * moe + 2 * 16 * 32 + 2 * 16 * 16
    assert count.matmul_params_touched(s) == params
    # attention over 8 positions: QK^T and dQ, dK at 8 channels, PV, dP and dV at 4, 2 x 8 x 8 a product, halved
    operations, nbytes = count.flash_step(s, rows=1.0, seq=8)
    assert operations == 4 * ((3 * 8 + 3 * 4) * 2 * 8 * 8 * 2 * 0.5)
    assert nbytes == 4 * (8 * 2 * ((2 * 8 + 2 * 4) + (4 * 8 + 4 * 4)) * 2)
    # experts: THREE products forward and six backward a row, in the two layers and the module's
    operations, nbytes = count.gmm_step(s, rows_here=10.0)
    assert operations == 3 * (9 * 2 * 16 * 12 * 10)
    assert nbytes == 3 * (3 * 2 * (3 * 16 * 12) * 2 + 3 * 10 * (3 * 16 + 3 * 12) * 2)
    assert count.train_flops_per_token(s, 8) == 6 * params + count.flash_step(s, 1.0, 8)[0] / 8
    # without the module: one attention layer, one expert layer, one pass through the head and the projection less
    without = dict(s, n_mtp=0)
    assert count.matmul_params_touched(s) - count.matmul_params_touched(without) == attention + moe + 16 * 32 + 2 * 16 * 16


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, module=True):
    """Two steps as the chip's trace names them: eight layers' ``flash_*`` (16
    forward kernels of 25 ms, eight of 40 and of 60), the grouped products,
    and operations that only MENTION a kernel; MOE_ROUTE events with the
    module's loss (``module`` False: as a program without the field)."""
    call = "%{} = bf16[1,32,16384,128] custom-call(bf16[1,32,16384,192] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 0.3))
        for layer in range(8):
            t = at + 0.30 + 0.16 * layer
            ops += [
                (call.format(f"flash_fwd.{2 + layer}"), t, 0.025), (call.format(f"flash_fwd.{20 + layer}"), t + 0.03, 0.025),
                (call.format(f"flash_dq.{2 + layer}"), t + 0.06, 0.040), (call.format(f"flash_dkv.{2 + layer}"), t + 0.10, 0.060),
            ]
        ops += [
            (call.format("jvp_jit_gmm__.4"), at + 1.60, 0.050),
            (call.format("transpose_jvp_jit_tgmm___.9"), at + 1.66, 0.030),
            ("%copy.9 = bf16[1,32,16384,128] copy(%flash_fwd.2)", at + 1.70, 0.001),
        ]
    def event(t, rows, mtp):  # noqa: E306
        fields = dict(name="MOE_ROUTE", t=t, rows_here=[rows] * 7, load_max=[1.5 * rows / 16] * 7,
                      load_mean=[rows / 16] * 7, buffer_rows=[10240.0] * 7)
        return dict(fields, mtp_nll=mtp) if module else fields
    return _trace_sources(cell, ops, [event(2.9, 8192.0, 10.25), event(4.9, 9216.0, 10.15), event(0.5, 9.0, 99.0)])


# ``latent_flash_roofline``, ``latent_moe_gmm_roofline`` and ``latent_step_mfu_pct`` were three more until PR 66:
# the cell is on the three folded readers' lists
NEW_READERS = ("xla_mtp_ms", "mtp_nll")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "moe_gmm_roofline", "quorum_ms", "commit_vote_ms", "step_device_ms", "device_idle_pct", "peak_hbm_gb",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean",
          "moe_route_ms", "moe_dispatch_ms", "moe_buffer_fill_pct", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms",
          "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_and_counter_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("flash_fwd_ms") == pytest.approx(16 * 25.0) and read("flash_dq_ms") == pytest.approx(8 * 40.0)
    assert read("flash_dkv_ms") == pytest.approx(8 * 60.0) and read("moe_gmm_ms") == pytest.approx(80.0)
    count, s = cell.architecture.latent_flops, sources["shapes"]
    for name, need, seconds in (
        ("flash_roofline", count.flash_step(s, 1, SEQ), 1.200),  # the second forward kernel's time counts, its work does not
        ("moe_gmm_roofline", count.gmm_step(s, 8704.0), 0.080),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    assert read("moe_rows_here_per_step") == pytest.approx(7 * 8704.0) and read("moe_load_max_over_mean") == pytest.approx(1.5)
    assert read("moe_buffer_fill_pct") == pytest.approx(100 * 8704.0 / 10240.0)
    busy = 0.3 + 1.200 + 0.080 + 0.001  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    assert 0 < read("step_mfu_pct") < 100
    # the module's mean loss over the window's events (the one before the window is not in it)
    assert read("mtp_nll") == pytest.approx(10.2) and abs(read("mtp_nll") - math.log(16160)) < 1.0
    # the readers of another architecture's shapes find nothing here
    for theirs in ("kda_roofline", "ssd_roofline", "swa_flash_roofline", "dsa_attn_roofline", "eva_flash_roofline"):
        assert read(theirs) is None, theirs


def _scoped_ops():
    """One step of 100 ms a device plane's way (``device_scopes.Op``), twice:
    the module's own work 7 + 5 ms, a kernel under the module's layer, the
    module's layer's stream, the head, an operation under no scope."""
    rows = [
        ("%fusion.1 = f32[8] fusion(%p)", 0, 7, "jit(_step)/jvp(tpuft.mtp)/dot_general:"),
        ("%fusion.2 = f32[8] fusion(%p)", 7, 12, "jit(_step)/transpose(jvp(tpuft.mtp))/dot_general:"),
        ("%flash_fwd.3 = bf16[8] custom-call(%q)", 12, 20, "jit(_step)/jvp(tpuft.mtp)/checkpoint/tpuft.mixer_glue/flash_fwd/pallas_call:"),
        ("%fusion.4 = f32[8] fusion(%p)", 20, 23, "jit(_step)/jvp(tpuft.mtp)/checkpoint/tpuft.stream/add:"),
        ("%fusion.5 = f32[8] fusion(%p)", 23, 33, "jit(_step)/jvp(tpuft.head)/dot_general:"),
        ("%copy.6 = f32[8] copy(%p)", 33, 34, ""),
    ]
    ops = []
    for step in range(2):
        for name, a, b, path in rows:
            start_ps, dur_ps = int((1.0 + 0.1 * step) * 1e12 + a * 1e9), int((b - a) * 1e9)
            ops.append(device_scopes.annotate(dict(
                name=name, start_ps=start_ps, dur_ps=dur_ps, start=start_ps * 1e-12, dur_s=dur_ps * 1e-12,
                tf_op=path, category="", source="",
            )))
    return ops


def test_the_modules_own_time_is_its_scopes_and_the_parts_still_tile_the_step(monkeypatch):
    """``xla_mtp_ms`` reads ``tpuft.mtp`` where it is INNERMOST: the module's
    layer names its parts and counts with theirs, a kernel under it is the
    kernels'.  With the part, ten parts, the unscoped rest and the kernels' own
    time are ``step_device_ms``."""
    planes = {0: _scoped_ops()}
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    steps = [dict(t_enter=1.0, t_exit=1.1), dict(t_enter=1.1, t_exit=1.2)]
    sources = dict(trace=dict(per_device={0: dict(ops=[])}, offset=0.0, traced_steps=[steps]), replicas=1, groups_share_chip=False)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("xla_mtp_ms") == pytest.approx(12.0)
    assert read("xla_stream_ms") == pytest.approx(3.0) and read("xla_head_ms") == pytest.approx(10.0)
    assert read("xla_unscoped_ms") == pytest.approx(1.0)
    parts = ("xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms", "moe_route_ms",
             "moe_dispatch_ms", "xla_layer_scan_ms", "optimizer_ms", "xla_mtp_ms")
    kernels = device_scopes.own_ms_per_step(sources, lambda op: op["kernel"])
    assert kernels == pytest.approx(8.0)
    assert sum(read(name) or 0.0 for name in parts) + read("xla_unscoped_ms") + kernels == pytest.approx(34.0)
    # a program with scopes and no module (any other cell): None, not 0
    planes[0] = [op for op in planes[0] if op["part"] != "mtp"]
    device_scopes._CUT.clear()
    assert read("xla_mtp_ms") is None and read("xla_head_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("lower" if name in ("xla_mtp_ms", "mtp_nll") else "higher")
    assert entry["layer"] == ("kernels" if name.endswith("_roofline") else "compiled step")
    assert entry["source"] == ("program_counter" if name == "mtp_nll" else "device_trace")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name, monkeypatch):
    """The parent commit has no such architecture, no ``tpuft.mtp`` and no
    ``mtp_nll`` in its events: the reader returns None, never raises, and the
    metric is left out."""
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    read = spec.load_metric(name, BENCH_DIR).read
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "keye2-ws1-seq16k", "trinitymini-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
        assert read(dict(sources, flight=[[]])) is None
    # this architecture's shapes over a trace without its kernels and events without the field
    assert read(_trace_sources(cell, ops, old_events)) is None
    if name == "mtp_nll":
        assert read(_made_trace(cell, module=False)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="joyai-llm-flash-ep16-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "512 tokens" in entry["why"] and "MTP" in entry["why"] and "192/128" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    # what this model has no part of stays without it: another architecture's
    # kernels and counting, another regime's end-to-end metric.  Named by what
    # they are, not by a closed list of today's readers
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_")), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)


def test_the_yardsticks_k_lies_between_its_two_readings(cell):
    arch = cell.architecture
    assert arch.READ_CONTROL_HIGH < arch.COARSE_RATIO_K < arch.READ_SOUND_LOW <= arch.READ_SOUND_HIGH
    # room on both sides: the worst sound seed and the nearest control each a quarter away at the least
    assert arch.READ_SOUND_LOW / arch.COARSE_RATIO_K > 1.25 and arch.COARSE_RATIO_K / arch.READ_CONTROL_HIGH > 1.25


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean", "mtp_nll"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    every selection bias in its slot (the module's too), the step's summary
    with the module's loss in the flight events, the float32 reference with
    the tie of ``loss`` to ``apply`` while the module is ON, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000049", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"flash_roofline", "step_mfu_pct", "xla_mtp_ms", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4 and checks["loss_tie"] <= 2e-5
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.4741, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["config"]["num_experts_per_tok"] <= toy["config"]["n_routed_experts"] <= toy["config"]["router_experts"]
