"""The configuration ``phi-4-mini-flash-reasoning-vp8-1x1``, its architecture
file, its counting of operations and bytes, its readers and the CPU rehearsal
of the cell ``phi4miniflash-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "phi4miniflash-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEQ = 16384


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == ["layer_pattern", "num_hidden_layers", "vocab_size"]
    # every published width unchanged
    assert (config["hidden_size"], config["intermediate_size"], config["num_attention_heads"], config["num_key_value_heads"]) == (2560, 10240, 40, 20)
    assert (config["sliding_window"], config["mb_per_layer"], config["layer_norm_eps"], config["tie_word_embeddings"]) == (512, 2, 1e-5, True)
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (8, 32)
    assert (config["vocab_size"], config["published"]["vocab_size"]) == (25008, 200064) and 8 * 25008 == 200064
    # all FIVE kinds of layer, in the published order, standing for the published layers 0-3 and 16-19
    assert config["layer_pattern"] == "MSMSMFGC" and set(config["layer_pattern"]) == set("MSFGC")
    assert config["published"]["layer_pattern"] == "MS" * 8 + "MF" + "GC" * 7 and len(config["published"]["layer_pattern"]) == 32
    assert config["layer_index"] == [0, 1, 2, 3, 16, 17, 18, 19]
    assert [config["published"]["layer_pattern"][i] for i in config["layer_index"]] == list(config["layer_pattern"])
    assert sorted(config["published"]) == sorted(config["reduced"])
    assumed = config["assumed"]
    assert (assumed["mamba_d_state"], assumed["mamba_d_conv"], assumed["mamba_expand"], assumed["mamba_dt_rank"]) == (16, 4, 2, 160)
    for key in ("learning_rate", "optimizer", "origin", "scan", "scan_init", "handed_on", "position", "differential", "biases",
                "memory_unit", "cross", "norm", "torch_dtype", "precision", "barrier", "cotangent_sum", "remat", "weights", "batch", "kernels",
                "model_code"):
        assert key in assumed, key
    assert assumed["learning_rate"] == 3e-4 and "float32" in assumed["cotangent_sum"] and "PAIRING" in assumed["differential"]
    assert "7.0 %" in config["stands_for"] and "13.3 %" in config["stands_for"] and "EIGHT chips" in config["stands_for"]
    assert config["parameters_here"].startswith("915,311,616")
    assert config["layout"] == dict(chips_per_group=1, groups_share_chip=False, fsdp=1)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k) != v} == {"num_hidden_layers", "vocab_size"}


@pytest.mark.parametrize(
    "key,value,why",
    [
        ("model_type", "llama", "model_type phi4flash"),
        ("mb_per_layer", 4, "every second layer"),
        ("tie_word_embeddings", False, "tied head"),
        ("mlp_bias", True, "no bias in the SwiGLU"),
        ("layer_pattern", "MSMSMFG", "spell num_hidden_layers"),
    ],
)
def test_the_adapter_refuses_a_configuration_it_was_not_built_for(cell, key, value, why):
    with pytest.raises(ValueError, match=why):
        cell.architecture.model(dict(cell.config, **{key: value}))


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 915_311_616  # the tied embedding ONCE
    assert arch.vocab(config) == 25008 and arch.KERNEL_PATHS == {"selscan+flash"}
    s = arch.shapes(config)
    assert (s["n_scan"], s["diff_windowed"], s["diff_full"], s["n_memory"], s["diff_cross"]) == (3, 2, 1, 1, 1)
    assert (s["head_dim"], s["scan_inner"], s["scan_state"], s["scan_dt_rank"], s["window"]) == (64, 5120, 16, 160, 512)
    count = arch.sambay_flops
    other = spec.load_cell("nemotron3nano-ws1-seq16k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config)) and not count.is_mine(None)
    # ISSUE 63's table: the mixers' matrices (the scan's less its conv, A_log and D), the SwiGLU's 78.64 M, the head
    scan = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    touched = 8 * 3 * 2560 * 10240 + 3 * scan + 3 * (2560 * 5120 + 2560 * 2560) + 2 * 2560 * 5120 + 2 * 2560 * 2560 + 2560 * 25008
    assert count.matmul_params_touched(s) == touched == 914841600
    assert 2560 * 25008 / touched == pytest.approx(0.070, abs=1e-3)  # the head's 7.0 %
    # attention: the live pairs of a window of 512 alone, heads of 64 for q and k and 128 for v, never padded
    assert count.live_pairs(SEQ, 512) == SEQ * 512 - 512 * 511 / 2 and count.live_pairs(SEQ) == SEQ * (SEQ + 1) / 2
    operations, nbytes = count.flash_step(s, 1.0, SEQ)
    pairs = 2 * count.live_pairs(SEQ, 512) + 2 * count.live_pairs(SEQ)
    assert operations == 2 * (3 * 64 + 3 * 128) * pairs * 40 and operations == pytest.approx(13.13e12, rel=1e-3)
    assert 2 * count.live_pairs(SEQ, 512) / pairs < 0.06  # the windowed launches are a twentieth of the pairs
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "compute"
    # the recurrence: 22 operations a (token, channel, state), 22 bytes a (token, channel): memory by the table's two peaks
    scan_operations, scan_bytes = count.selscan_step(s, 1.0, SEQ)
    assert scan_operations == 22 * 16 * 3 * SEQ * 5120 and scan_bytes == 22 * 3 * SEQ * 5120
    assert flops.roofline_pct(scan_operations, scan_bytes, 1.0, "TPU v5 lite")["bound"] == "memory"
    per_token = count.train_flops_per_token(s, SEQ)
    assert per_token == 6 * touched + (operations + scan_operations) / SEQ
    # a step: 103 TFLOP, 0.52 s at the chip's peak
    assert per_token * SEQ == pytest.approx(103.2e12, rel=1e-3) and per_token * SEQ / 197e12 == pytest.approx(0.524, abs=2e-3)


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=4.0), dict(t_enter=4.0, t_exit=7.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, mine=True):
    """Two steps as the chip's trace names them: three scans' ``selscan_fwd``
    (10 ms) and ``selscan_bwd`` (30 ms), two windowed launches' and two whole
    launches' three flash kernels, and operations that only MENTION a kernel;
    the step's events with ``decay_min`` (``mine`` False: as a program without
    the field)."""
    call = "%{} = bf16[1,16384,5120] custom-call(bf16[1,16384,5120] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 3.0 * step
        ops.append(("%fusion.9 = bf16[16384,2560] fusion(%p)", at, 1.0))
        for n in range(3):
            ops += [(call.format(f"selscan_fwd.{2 + n}"), at + 1.0 + 0.05 * n, 0.010), (call.format(f"selscan_bwd.{2 + n}"), at + 1.01 + 0.05 * n, 0.030)]
        for n in range(2):
            t = at + 1.2 + 0.1 * n
            ops += [(call.format(f"flash_win_fwd.{2 + n}"), t, 0.002), (call.format(f"flash_win_dq.{2 + n}"), t + 0.002, 0.003),
                    (call.format(f"flash_win_dkv.{2 + n}"), t + 0.005, 0.004), (call.format(f"flash_fwd.{2 + n}"), t + 0.01, 0.020),
                    (call.format(f"flash_dq.{2 + n}"), t + 0.03, 0.030), (call.format(f"flash_dkv.{2 + n}"), t + 0.06, 0.035)]
        ops.append(("%copy.9 = bf16[1,16384,5120] copy(%selscan_fwd.2)", at + 2.95, 0.001))
    event = lambda t, low: dict(name="MOE_ROUTE", t=t, **({"decay_min": low, "lambda": 0.7} if mine else {}))  # noqa: E731
    return _trace_sources(cell, ops, [event(3.9, -1.6), event(6.9, -1.9), event(0.5, -99.0)])


# ``sambay_step_mfu_pct`` was the eighth until PR 66: the cell is on ``step_mfu_pct``'s list (and on no list of
# ``flash_roofline``: ``sambay_flash_roofline`` counts the six launches of a step, the windowed ones too)
NEW_READERS = ("selscan_fwd_ms", "selscan_bwd_ms", "selscan_roofline", "sambay_flash_roofline", "selscan_decay_min",
               "xla_mixer_diff_ms")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "step_device_ms", "device_idle_pct", "peak_hbm_gb", "quorum_ms", "commit_vote_ms",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms",
          "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_and_counter_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("selscan_fwd_ms") == pytest.approx(30.0) and read("selscan_bwd_ms") == pytest.approx(90.0)
    # the accepted readers of the whole launches' kernels find them in this cell (``flash_fwd`` does not match
    # ``flash_win_fwd``); the windowed launches' time is in ``sambay_flash_roofline``'s six programs: Trinity's
    # ``swa_flash_ms`` would read them by name, but its own test holds its list to Trinity's cell alone
    assert read("flash_fwd_ms") == pytest.approx(40.0) and read("flash_dq_ms") == pytest.approx(60.0) and read("flash_dkv_ms") == pytest.approx(70.0)
    assert read("swa_flash_ms") == pytest.approx(18.0)
    count, s = cell.architecture.sambay_flops, sources["shapes"]
    assert read("selscan_roofline") == pytest.approx(flops.roofline_pct(*count.selscan_step(s, 1, SEQ), 0.120, "TPU v5 lite")["pct"])
    assert read("sambay_flash_roofline") == pytest.approx(flops.roofline_pct(*count.flash_step(s, 1, SEQ), 0.188, "TPU v5 lite")["pct"])
    assert 0 < read("selscan_roofline") < 100 and 0 < read("sambay_flash_roofline") < 100
    busy = 1.0 + 0.120 + 0.188 + 0.001  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    assert 0 < read("step_mfu_pct") < 100
    assert read("selscan_decay_min") == -1.9  # the window's events: the one before the window is not in it
    for theirs in ("swa_flash_roofline", "ssd_fwd_ms", "gdn_roofline", "moe_gmm_roofline", "moe_gmm_ms"):
        assert read(theirs) is None, theirs


def test_the_differential_combine_is_read_apart_from_the_glue_around_it(cell, monkeypatch):
    """``tpuft.mixer_diff`` lies inside ``tpuft.mixer_glue`` and the innermost
    scope is an operation's part: ``xla_mixer_glue_ms`` leaves it out, so
    ``xla_mixer_diff_ms`` reads it and the parts tile the step again."""
    from ftbench import device_scopes

    layer = "jit(_step)/jvp(tpuft.layers)/while/body/closed_call/checkpoint/tpuft.mixer_glue"
    made = []
    for step, diff_ms in enumerate((4, 6)):
        for start_ms, dur_ms, name, path in (
            (0, 10, "%fusion.1 = bf16[16384,5120] fusion(%p)", layer + "/mul:"),
            (100, diff_ms, "%fusion.2 = f32[16384,20,128] fusion(%o)", layer + "/tpuft.mixer_diff/sub:"),
            (200, 30, "%flash_fwd.3 = bf16[1,40,16384,128] custom-call(%q)", layer + "/flash_fwd/pallas_call:"),
        ):
            start_ps = int((1.0 + 3.0 * step) * 1e12 + start_ms * 1e9)
            made.append(device_scopes.annotate(dict(
                name=name, start_ps=start_ps, dur_ps=int(dur_ms * 1e9), start=start_ps * 1e-12, dur_s=dur_ms * 1e-3,
                tf_op=path, category="loop fusion", source="",
            )))
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {0: made})
    sources = _trace_sources(cell, [])
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("xla_mixer_diff_ms") == pytest.approx(5.0) and read("xla_mixer_glue_ms") == pytest.approx(10.0)
    kernels = device_scopes.own_ms_per_step(sources, lambda op: op["kernel"])
    assert read("xla_mixer_diff_ms") + read("xla_mixer_glue_ms") + read("xla_unscoped_ms") + kernels == pytest.approx(45.0)
    # scopes, and nothing under this one (any other model's trace): left out, not 0
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {0: [op for op in made if op["part"] != "mixer_diff"]})
    assert read("xla_mixer_diff_ms") is None and read("xla_mixer_glue_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("lower" if name in ("selscan_fwd_ms", "selscan_bwd_ms", "selscan_decay_min", "xla_mixer_diff_ms") else "higher")
    assert entry["layer"] == ("compiled step" if name == "xla_mixer_diff_ms" else "kernels")
    assert entry["source"] == ("program_counter" if name == "selscan_decay_min" else "device_trace")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such architecture, no ``selscan_*`` kernel and
    no float ``decay_min`` in its events: the reader returns None, never
    raises, and the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%flash_fwd.1 = bf16[2048,4096] custom-call(%p)", 4.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=3.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0], decay_min=[-44.5, 0.0])]
    read = spec.load_metric(name, BENCH_DIR).read
    for other in ("mistral7b-ws1-steady", "nemotron3nano-ws1-seq16k", "trinitymini-ws1-seq16k", "qwen3next-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
        assert read(dict(sources, flight=[[]])) is None
    assert read(dict(_trace_sources(cell, ops, old_events), trace=None)) is None
    if name == "selscan_decay_min":
        assert read(_made_trace(cell, mine=False)) is None
    if name in ("selscan_fwd_ms", "selscan_bwd_ms", "selscan_roofline"):
        assert read(_trace_sources(cell, ops)) is None  # this architecture's shapes over a trace without the kernels


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="phi-4-mini-flash-reasoning-vp8-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "16,384" in entry["why"] and "64/128" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    assert config["file"] == "ftbench/configs/phi-4-mini-flash-reasoning-vp8-1x1.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED + NEW_READERS:
        assert CELL in listed[name], name
    assert len(bench["per_layer"]) <= 128
    # what this model has no part of stays without it: another architecture's counting, the
    # experts' readers, Trinity's window readers, another regime's end-to-end metric
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_", "moe_", "latent_", "mtp_", "eva_", "gdn_", "loop_")), name
            assert name not in ("flash_roofline", "moe_gmm_roofline", "xla_mixer_pool_ms", "xla_mtp_ms", "xla_loop_gate_ms"), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)


def test_the_yardsticks_k_lies_between_its_two_readings(cell):
    arch = cell.architecture
    assert arch.READ_CONTROL_HIGH < arch.COARSE_RATIO_K < arch.READ_SOUND_LOW <= arch.READ_SOUND_HIGH
    # room on both sides: the worst sound seed and the nearest control each a quarter away at the least
    assert arch.READ_SOUND_LOW / arch.COARSE_RATIO_K > 1.25 and arch.COARSE_RATIO_K / arch.READ_CONTROL_HIGH > 1.25


def test_the_backward_limits_lie_between_their_two_readings():
    """``sambay_forward_check.py``'s backward part: the step's gradient under
    ``STEP_LIMIT`` and its control on the coarse copy over it, the float32
    program under ``FLOAT32_LIMIT`` and the step's least reading over it, each
    with at least twice of room."""
    from ftbench.tests import sambay_forward_check as check

    assert 2 * check.READ_STEP_HIGH < check.STEP_LIMIT < check.READ_CONTROL_LOW / 2
    assert 2 * check.READ_FLOAT32_HIGH < check.FLOAT32_LIMIT < check.READ_STEP_LOW / 2
    assert set(check.STEP_HELD) == {path[-1] for path in check.GRAD_LEAVES} - {"q1"}


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "selscan_decay_min"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary with ``decay_min`` and ``lambda`` in the flight events,
    the float32 reference with the tie of ``loss`` to ``apply``, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000063", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"selscan_fwd_ms", "selscan_roofline", "step_mfu_pct", "flash_fwd_ms", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4 and checks["loss_tie"] <= 2e-5
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.3857, abs=1e-3)
