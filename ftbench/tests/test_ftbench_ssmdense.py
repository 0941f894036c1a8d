"""The configuration ``granite-4.0-h-micro-vp4-1x1``, its architecture file, its
counting of operations and bytes, its readers and the CPU rehearsal of the cell
``granite4hmicro-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "granite4hmicro-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEQ = 16384
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    # every published width unchanged
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]) == (2048, 32, 8)
    assert config["intermediate_size"] == config["shared_intermediate_size"] == 8192
    assert (config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"], config["mamba_n_groups"]) == (64, 64, 128, 1)
    assert (config["mamba_d_conv"], config["mamba_expand"], config["mamba_chunk_size"], config["rms_norm_eps"]) == (4, 2, 256, 1e-5)
    multipliers = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")
    assert tuple(config[k] for k in multipliers) == (12, 0.22, 0.015625, 8)
    assert config["tie_word_embeddings"] is True and config["position_embedding_type"] == "nope" and config["num_local_experts"] == 0
    # one whole period of the published pattern: attention where the index is 5 modulo 10
    published = config["published"]["layer_types"]
    assert config["layer_types"] == published[:10] == PERIOD and published == PERIOD * 4
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (10, 40)
    # a quarter of the vocabulary, with a quarter of the depth: over the floor of an eighth
    assert (config["vocab_size"], config["published"]["vocab_size"]) == (25088, 100352) and 4 * 25088 == 100352
    assumed = config["assumed"]
    for key in ("learning_rate", "optimizer", "scan_chunk", "scan_chunk_why", "time_step_min", "time_step_max", "time_step_floor", "ssm_init",
                "time_step_limit", "gated_norm", "embedding_std", "weights", "multipliers", "residual_stream", "precision", "position",
                "barrier", "remat", "cotangent_sum", "torch_dtype", "batch", "kernels", "unread", "model_code"):
        assert key in assumed, key
    assert assumed["learning_rate"] == 3e-4 and assumed["scan_chunk"] in (128, 256) and "SAME leaf" in assumed["embedding_std"]
    assert "FOUR pipeline stages" in config["stands_for"] and "FOUR chips share" in config["stands_for"] and "6.4 %" in config["stands_for"]
    assert config["parameters_here"].startswith("797,850,560")
    assert config["layout"] == dict(chips_per_group=1, groups_share_chip=False, fsdp=1)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k) != v} == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


@pytest.mark.parametrize(
    "key,value,why",
    [
        ("model_type", "nemotron_h", "model_type granitemoehybrid"),
        ("num_local_experts", 8, "dense member"),
        ("tie_word_embeddings", False, "tied head"),
        ("position_embedding_type", "rope", "no position encoding"),
        ("mamba_proj_bias", True, "without a bias"),
        ("layer_types", PERIOD[:9], "an entry a layer"),
        ("mamba_expand", 4, "fill mamba_expand"),
        ("intermediate_size", 4096, "intermediate_size = shared_intermediate_size"),
    ],
)
def test_the_adapter_refuses_a_configuration_it_was_not_built_for(cell, key, value, why):
    with pytest.raises(ValueError, match=why):
        cell.architecture.model(dict(cell.config, **{key: value}))


def test_the_four_multipliers_and_the_chunk_reach_the_model_from_the_file(cell):
    arch, config = cell.architecture, cell.config
    other = dict(config, embedding_multiplier=7, residual_multiplier=0.5, attention_multiplier=0.2, logits_scaling=3)
    made = arch.model_config(other)
    assert (made.embedding_multiplier, made.residual_multiplier, made.attention_multiplier, made.logits_scaling) == (7, 0.5, 0.2, 3)
    made = arch.model_config(config)
    assert (made.embedding_multiplier, made.residual_multiplier, made.attention_multiplier, made.logits_scaling) == (12, 0.22, 0.015625, 8)
    assert made.chunk == config["assumed"]["scan_chunk"] and made.layer_types == tuple(PERIOD) and made.ssm_groups == 1
    assert (made.head_dim, made.ffn_hidden, made.ssm_inner, made.ssm_conv_width) == (64, 8192, 4096, 4352)


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 797_850_560  # the tied embedding ONCE
    assert arch.vocab(config) == 25088 and arch.KERNEL_PATHS == {"ssd+flash"}
    s = arch.shapes(config)
    assert "n_ssm" not in s and (s["n_mamba"], s["n_attention"], s["ssm_groups"], s["tied"]) == (9, 1, 1, True)
    count = arch.ssmdense_flops
    assert arch.flops is count and count.is_mine(s) and not count.is_mine(None) and not count.is_mine({})
    # Nemotron's and Phi-4-mini-flash's shapes are not this architecture's, nor these Nemotron's
    for other in ("nemotron3nano-ws1-seq16k", "phi4miniflash-ws1-seq16k"):
        theirs = spec.load_cell(other)
        assert not count.is_mine(theirs.architecture.shapes(theirs.config)) and not theirs.architecture.flops.is_mine(s)
    # ISSUE 69's table: a Mamba-2 layer's matrices 17,432,576 + 8,388,608, the attention layer's 10,485,760, the SwiGLU's
    # 50,331,648 in every layer, the head 51,380,224 ONCE
    mamba, attention, ffn, head = 2048 * 8512 + 4096 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512, 3 * 2048 * 8192, 2048 * 25088
    assert (mamba, attention, ffn, head) == (25_821_184, 10_485_760, 50_331_648, 51_380_224)
    touched = 10 * ffn + 9 * mamba + attention + head
    assert count.matmul_params_touched(s) == touched == 797_573_120
    assert head / touched == pytest.approx(0.0644, abs=1e-4)  # the head's 6.4 %, as in the whole model
    whole = 40 * ffn + 36 * mamba + 4 * attention + 2048 * 100352
    assert 2048 * 100352 / whole == pytest.approx(0.0644, abs=1e-4)
    # the scan and the attention are ``ssm_flops``' own arithmetic at these sizes: called, not restated
    ssm = spec.load_architecture("ssm_hybrid_moe", BENCH_DIR).ssm_flops
    assert count.ssd_step(s, 1.0, SEQ) == ssm.ssd_step(dict(s, n_ssm=9), 1.0, SEQ)
    assert count.flash_step(s, 1.0, SEQ) == ssm.flash_step(dict(s, n_ssm=9), 1.0, SEQ)
    operations, nbytes = count.ssd_step(s, 1.0, SEQ)
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "memory"
    operations, nbytes = count.flash_step(s, 1.0, SEQ)
    assert operations == 6 * 2 * SEQ * SEQ * 64 * 32 / 2 and flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "compute"
    per_token = count.train_flops_per_token(s, SEQ)
    assert per_token == 6 * touched + (count.ssd_step(s, 1.0, SEQ)[0] + count.flash_step(s, 1.0, SEQ)[0]) / SEQ
    # a step: 83.1 TFLOP, 0.42 s at the chip's peak
    assert per_token * SEQ == pytest.approx(83.11e12, rel=1e-3) and per_token * SEQ / 197e12 == pytest.approx(0.422, abs=2e-3)


def test_counting_by_hand_at_toy_widths(cell):
    """``ssmdense_flops`` against a count by hand: ONE group of 4 heads of 4, a
    state of 8, chunks of 4, 8 positions; two Mamba-2 layers and one attention
    layer, a SwiGLU of 12 in each, a vocabulary of 32."""
    count = cell.architecture.ssmdense_flops
    s = dict(dim=16, n_mamba=2, n_attention=1, ssm_heads=4, ssm_head_dim=4, ssm_state=8, ssm_groups=1, chunk=4,
             n_heads=4, n_kv_heads=2, head_dim=4, ffn_hidden=12, vocab_size=32, tied=True)
    # forward, a token: C B^T over the 2.5 tokens of the chunk before it (8 a pair, ONCE for the four heads: 8 x 2.5 x 2),
    # the masked product with dt x (2 x 4 x 2.5 a head), the state's read-out and update (2 x 8 x 4 each a head)
    forward = 2 * 8 * 2.5 + 4 * (2 * 4 * 2.5 + 2 * 2 * 8 * 4)
    operations, nbytes = count.ssd_step(s, rows=1.0, seq=8)
    assert operations == 2 * 3 * forward * 8
    operands = (4 * 4 + 2 * 8) * 2 + 2 * 4 * 4  # dt x of four heads, B and C ONCE in bfloat16; the log decay twice in float32
    states = 4 * 4 * 8 * 4 / 4  # a chunk's starting state in float32, a token's share
    assert nbytes == 2 * 8 * ((operands + 4 * 4 * 2 + states) + (operands + states + 4 * 4 * 2 + operands))
    # attention: six products of 2 S S D a query head, halved; q, o (4 heads) and k, v (2) of 4
    operations, nbytes = count.flash_step(s, rows=1.0, seq=8)
    assert operations == 6 * 2 * 8 * 8 * 4 * 4 / 2
    assert nbytes == 8 * 4 * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) * 2
    params = 3 * 3 * 16 * 12 + 2 * (16 * (2 * 16 + 2 * 8 + 4) + 16 * 16) + (2 * 16 * 16 + 2 * 16 * 8) + 16 * 32
    assert count.matmul_params_touched(s) == params
    assert count.train_flops_per_token(s, 8) == 6 * params + (count.ssd_step(s, 1.0, 8)[0] + count.flash_step(s, 1.0, 8)[0]) / 8


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=4.0), dict(t_enter=4.0, t_exit=7.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, mine=True):
    """Two steps as the chip's trace names them: nine ``ssd_fwd`` (4 ms) and
    nine ``ssd_bwd`` (10 ms), the attention layer's three flash kernels, and
    operations that only MENTION a kernel; the step's events with ``decay_min``
    (``mine`` False: as a program without the field)."""
    call = "%{} = bf16[1,64,16384,64] custom-call(bf16[1,64,16384,64] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 3.0 * step
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 1.0))
        for n in range(9):
            ops += [(call.format(f"ssd_fwd.{2 + n}"), at + 1.0 + 0.02 * n, 0.004), (call.format(f"ssd_bwd.{2 + n}"), at + 1.005 + 0.02 * n, 0.010)]
        ops += [(call.format("flash_fwd.2"), at + 1.3, 0.020), (call.format("flash_dq.2"), at + 1.33, 0.030), (call.format("flash_dkv.2"), at + 1.37, 0.035)]
        ops.append(("%copy.9 = bf16[1,64,16384,64] copy(%ssd_fwd.2)", at + 2.95, 0.001))
    event = lambda t, low: dict(name="MOE_ROUTE", t=t, **({"decay_min": low} if mine else {}))  # noqa: E731
    return _trace_sources(cell, ops, [event(3.9, -1.6), event(6.9, -1.9), event(0.5, -99.0)])


NEW_READERS = ("ssmdense_ssd_fwd_ms", "ssmdense_ssd_bwd_ms", "ssmdense_ssd_roofline", "xla_mixer_conv_ms", "xla_mixer_gate_ms",
               "ssmdense_decay_min")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "step_device_ms", "device_idle_pct", "peak_hbm_gb", "quorum_ms",
          "commit_vote_ms", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms",
          "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_and_counter_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("ssmdense_ssd_fwd_ms") == pytest.approx(36.0) and read("ssmdense_ssd_bwd_ms") == pytest.approx(90.0)
    assert read("flash_fwd_ms") == pytest.approx(20.0) and read("flash_dq_ms") == pytest.approx(30.0) and read("flash_dkv_ms") == pytest.approx(35.0)
    count, s = cell.architecture.ssmdense_flops, sources["shapes"]
    assert read("ssmdense_ssd_roofline") == pytest.approx(flops.roofline_pct(*count.ssd_step(s, 1, SEQ), 0.126, "TPU v5 lite")["pct"])
    assert read("flash_roofline") == pytest.approx(flops.roofline_pct(*count.flash_step(s, 1, SEQ), 0.085, "TPU v5 lite")["pct"])
    assert 0 < read("ssmdense_ssd_roofline") < 100 and 0 < read("flash_roofline") < 100
    busy = 1.0 + 0.126 + 0.085 + 0.001  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    assert 0 < read("step_mfu_pct") < 100
    assert read("ssmdense_decay_min") == -1.9  # the window's events: the one before the window is not in it
    # Nemotron's roofline reader is Nemotron's (its ``is_mine`` asks for ``n_ssm``), the experts' readers find none
    for theirs in ("ssd_roofline", "selscan_roofline", "gdn_roofline", "moe_gmm_roofline", "moe_gmm_ms"):
        assert read(theirs) is None, theirs


def test_the_convolution_and_the_gated_norm_are_read_apart_from_the_glue_around_them(cell, monkeypatch):
    """``tpuft.mixer_conv`` and ``tpuft.mixer_gate`` lie inside
    ``tpuft.mixer_glue`` and the innermost scope is an operation's part:
    ``xla_mixer_glue_ms`` leaves them out, the two readers read them, and the
    parts tile the step."""
    from ftbench import device_scopes

    layer = "jit(_step)/jvp(tpuft.layers)/while/body/closed_call/checkpoint/tpuft.mixer_glue"
    made = []
    for step, conv_ms in enumerate((14, 18)):
        for start_ms, dur_ms, name, path in (
            (0, 10, "%fusion.1 = f32[16384,64] fusion(%p)", layer + "/softplus:"),
            (100, conv_ms, "%fusion.2 = bf16[16384,4352] fusion(%o)", layer + "/tpuft.mixer_conv/mul:"),
            (200, 8, "%fusion.3 = bf16[16384,4096] fusion(%o)", layer + "/tpuft.mixer_gate/rsqrt:"),
            (300, 30, "%ssd_fwd.3 = bf16[1,64,16384,64] custom-call(%q)", layer + "/ssd_fwd/pallas_call:"),
        ):
            start_ps = int((1.0 + 3.0 * step) * 1e12 + start_ms * 1e9)
            made.append(device_scopes.annotate(dict(
                name=name, start_ps=start_ps, dur_ps=int(dur_ms * 1e9), start=start_ps * 1e-12, dur_s=dur_ms * 1e-3,
                tf_op=path, category="loop fusion", source="",
            )))
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {0: made})
    sources = _trace_sources(cell, [])
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("xla_mixer_conv_ms") == pytest.approx(16.0) and read("xla_mixer_gate_ms") == pytest.approx(8.0)
    assert read("xla_mixer_glue_ms") == pytest.approx(10.0)
    kernels = device_scopes.own_ms_per_step(sources, lambda op: op["kernel"])
    assert read("xla_mixer_conv_ms") + read("xla_mixer_gate_ms") + read("xla_mixer_glue_ms") + read("xla_unscoped_ms") + kernels == pytest.approx(64.0)
    # scopes, and nothing under these two (any other model's trace): left out, not 0
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {0: [op for op in made if op["part"] == "mixer_glue"]})
    assert read("xla_mixer_conv_ms") is None and read("xla_mixer_gate_ms") is None and read("xla_mixer_glue_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("higher" if name == "ssmdense_ssd_roofline" else "lower")
    assert entry["layer"] == ("compiled step" if name.startswith("xla_") else "kernels")
    assert entry["source"] == ("program_counter" if name == "ssmdense_decay_min" else "device_trace")
    assert entry["unit"] == {"ssmdense_ssd_roofline": "%", "ssmdense_decay_min": "nats"}.get(name, "ms")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such architecture, no ``tpuft.mixer_conv`` and
    no cell whose shapes are these: the reader returns None, never raises, and
    the metric is left out.  On ANOTHER architecture's cell it reads nothing
    either, whatever the trace holds: Nemotron's ``ssd_fwd`` launches and
    Phi-4-mini-flash's float ``decay_min`` are theirs."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%flash_fwd.1 = bf16[2048,4096] custom-call(%p)", 4.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=3.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0], decay_min=[-44.5, 0.0])]
    read = spec.load_metric(name, BENCH_DIR).read
    made = _made_trace(cell)
    for other in ("mistral7b-ws1-steady", "nemotron3nano-ws1-seq16k", "phi4miniflash-ws1-seq16k", "qwen3next-ws1-seq16k"):
        theirs = spec.load_cell(other)
        for sources in (_trace_sources(cell, ops, old_events), made):
            sources = dict(sources, shapes=theirs.architecture.shapes(theirs.config))
            assert read(sources) is None
            assert read(dict(sources, trace=None)) is None
            assert read(dict(sources, flight=[[]])) is None
    assert read(dict(_trace_sources(cell, ops, old_events), trace=None)) is None
    assert read(_trace_sources(cell, ops, old_events)) is None  # this architecture's shapes over a trace and events without them
    if name == "ssmdense_decay_min":
        assert read(_made_trace(cell, mine=False)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="granite-4.0-h-micro-vp4-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "16,384" in entry["why"] and "ONE group of 64" in entry["why"] and "9 scans to 1" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert config["file"] == "ftbench/configs/granite-4.0-h-micro-vp4-1x1.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED + NEW_READERS:
        assert CELL in listed[name], name
    assert len(bench["per_layer"]) <= 128
    # what this model has no part of stays without it: another architecture's counting, the experts'
    # readers, Nemotron's scan readers (its own test holds their lists to its cell), another regime's metric
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_", "moe_", "latent_", "mtp_", "eva_", "gdn_", "loop_", "selscan_", "sambay_", "prerouted_")), name
            assert name not in ("moe_gmm_roofline", "xla_mixer_pool_ms", "xla_mixer_diff_ms", "xla_mtp_ms", "xla_loop_gate_ms"), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)


def test_the_yardsticks_k_lies_between_its_two_readings(cell):
    arch = cell.architecture
    assert arch.READ_CONTROL_HIGH < arch.COARSE_RATIO_K < arch.READ_SOUND_LOW <= arch.READ_SOUND_HIGH
    # room on both sides: the worst sound seed and the nearest control each a quarter away at the least
    assert arch.READ_SOUND_LOW / arch.COARSE_RATIO_K > 1.25 and arch.COARSE_RATIO_K / arch.READ_CONTROL_HIGH > 1.25


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "ssmdense_decay_min"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary with ``decay_min`` in the flight events, the float32
    reference with the tie of ``loss`` to ``apply``, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000069", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"ssmdense_ssd_fwd_ms", "ssmdense_ssd_roofline", "step_mfu_pct", "flash_fwd_ms", "step_device_ms", "xla_mixer_conv_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4 and checks["loss_tie"] <= 2e-5
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.545, abs=1e-3)
