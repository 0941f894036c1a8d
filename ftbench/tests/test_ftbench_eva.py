"""The configuration ``evabyte-6.5b-1x1``, its architecture file, its counting
of operations and bytes, its readers and the CPU rehearsal of the cell
``evabyte-ws1-seq32k``.  No number here is a device's."""

import json
import math
import os

import pytest

from ftbench import device_scopes, flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "evabyte-ws1-seq32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width, the window, the chunk, the slices
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "window_size",
          "chunk_size", "num_pred_heads", "vocab_size")
SEQ = 32768


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_one_cut_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"]) == ["num_hidden_layers"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["intermediate_size"], config["num_attention_heads"]) == (4096, 11008, 32)
    assert (config["attention_class"], config["window_size"], config["chunk_size"], config["num_pred_heads"]) == ("eva", 2048, 16, 8)
    assert (config["vocab_size"], config["max_seq_length"], config["rope_theta"], config["rms_norm_eps"]) == (320, SEQ, 100000, 1e-5)
    assert (config["norm_add_unit_offset"], config["fp32_skip_add"], config["fp32_logits"], config["mixedp_attn"]) == (True,) * 4
    # the floor: the pattern's period is one layer and there is no leading dense layer
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (4, 32)
    for key in ("learning_rate", "optimizer", "origin", "pooling", "rope_before_pooling", "windows", "head", "precision",
                "weights", "batch", "kernels", "model_code"):
        assert key in config["assumed"], key
    assert config["assumed"]["learning_rate"] == 3e-4
    assert "eight pipeline stages" in config["stands_for"] and "32,768" in config["stands_for"]
    assert config["parameters_here"].startswith("821,366,784")
    assert config["layout"] == dict(chips_per_group=1, groups_share_chip=False, fsdp=1)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k) != v} == {"num_hidden_layers"}
        assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]


@pytest.mark.parametrize(
    "key,value,why",
    [
        ("attention_class", "softmax", "attention_class eva"),
        ("num_key_value_heads", 8, "its own k and v"),
        ("attention_bias", True, "without a bias"),
        ("norm_add_unit_offset", False, "weight 1 \\+ g"),
        ("window_size", 2040, "whole chunks"),
    ],
)
def test_the_adapter_refuses_a_configuration_it_was_not_built_for(cell, key, value, why):
    with pytest.raises(ValueError, match=why):
        cell.architecture.model(dict(cell.config, **{key: value}))


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 821_366_784
    assert arch.vocab(config) == 320 and arch.KERNEL_PATHS == {"flash"}
    s = arch.shapes(config)
    assert (s["n_layers"], s["n_heads"], s["head_dim"], s["eva_window"], s["eva_chunk"], s["n_pred_heads"]) == (4, 32, 128, 2048, 16, 8)
    count = arch.eva_flops
    other = spec.load_cell("trinitymini-ws1-seq16k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config)) and not count.is_mine(None)
    assert not other.architecture.swa_flops.is_mine(s)
    # ISSUE 52: a layer's matrices 202.38 M, the head's eight slices 10.49 M
    layer, head = 4 * 4096 * 4096 + 3 * 4096 * 11008, 4096 * 8 * 320
    assert count.matmul_params_touched(s) == 4 * layer + head == 819_986_432 and layer == 202_375_168
    # the live pairs a head: 16 windows' triangles and the summaries of the windows before, about half each
    assert count.live_pairs(s, SEQ) == 16 * 2048 * 2049 / 2 + 2048 * 128 * (16 * 15 / 2) == 65_028_096
    assert count.live_pairs(s, SEQ) / (SEQ * (SEQ + 1) / 2) == pytest.approx(1 / 8.26, rel=1e-2)
    assert count.live_pairs(s, 2048) == 2048 * 2049 / 2  # a window that covers the sequence: causal attention
    operations, nbytes = count.flash_step(s, 1.0, SEQ)
    assert operations == 4 * 12 * 65_028_096 * 128 * 32 and operations == pytest.approx(1.2785e13, rel=1e-4)
    assert nbytes == 4 * 128 * 32 * (6 * SEQ + 6 * (SEQ + 2048)) * 2
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "compute"
    # of the step's operations the mechanism is 7.3 %: by construction not the majority
    per_token = count.train_flops_per_token(s, SEQ)
    assert per_token == 6 * 819_986_432 + operations / SEQ + 4 * 9 * 2 * 4096
    assert operations / SEQ / per_token == pytest.approx(0.0735, abs=1e-3)
    # a byte's pairs: ISSUE 52's 1,985 a head, 32.5 MFLOP forward a layer
    assert count.live_pairs(s, SEQ) / SEQ == pytest.approx(1984.5, abs=0.1)
    assert 2 * 2 * count.live_pairs(s, SEQ) / SEQ * 128 * 32 == pytest.approx(32.5e6, rel=2e-3)


def test_counting_by_hand_at_toy_widths(cell):
    """``eva_flops`` against a count by hand: 2 heads of 4, windows of 8 in
    chunks of 2, 3 layers, 2 slices of 5."""
    count = cell.architecture.eva_flops
    s = dict(dim=8, n_layers=3, n_heads=2, head_dim=4, ffn_hidden=12, eva_window=8, eva_chunk=2, n_pred_heads=2, vocab_size=5)
    assert count.matmul_params_touched(s) == 3 * (4 * 8 * 8 + 3 * 8 * 12) + 8 * 2 * 5
    # 24 positions: three windows' triangles of 36 pairs, and 8 rows x 4 summaries x (0 + 1 + 2) windows before
    assert count.live_pairs(s, 24) == 3 * 36 + 8 * 4 * 3 == 204
    by_position = sum(
        sum(1 for j in range(24) if j // 8 == i // 8 and j <= i) + sum(1 for c in range(12) if c * 2 // 8 < i // 8)
        for i in range(24)
    )
    assert by_position == 204
    operations, nbytes = count.flash_step(s, rows=2.0, seq=24)
    assert operations == 3 * (6 * 2 * 204 * 4 * 2 * 2)
    assert nbytes == 3 * (2 * 4 * 2 * (6 * 24 + 6 * (24 + 12)) * 2)
    assert count.pool_flops_per_token(s) == 3 * 3 * 3 * 2 * 2 * 4
    assert count.train_flops_per_token(s, 24) == 6 * count.matmul_params_touched(s) + count.flash_step(s, 1.0, 24)[0] / 24 + 432


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, further=True):
    """Two steps as the chip's trace names them: four layers' ``eva_*`` (a
    forward kernel of 20 ms, a ``dq`` of 25, a ``dkv`` of 30 a layer) and
    operations that only MENTION a kernel; the step's events with the further
    slices' loss (``further`` False: as a program without the field)."""
    call = "%{} = bf16[1,32,32768,128] custom-call(bf16[1,32,32768,128] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[32768,4096] fusion(%p)", at, 1.2))
        for layer in range(4):
            t = at + 1.2 + 0.1 * layer
            ops += [
                (call.format(f"eva_fwd.{2 + layer}"), t, 0.020), (call.format(f"eva_dq.{2 + layer}"), t + 0.02, 0.025),
                (call.format(f"eva_dkv.{2 + layer}"), t + 0.05, 0.030),
            ]
        ops.append(("%copy.9 = bf16[1,32,34816,128] copy(%eva_fwd.2)", at + 1.7, 0.001))
    event = lambda t, nll: dict(name="MOE_ROUTE", t=t, **(dict(multibyte_nll=nll) if further else {}))  # noqa: E731
    return _trace_sources(cell, ops, [event(2.9, 6.05), event(4.9, 5.95), event(0.5, 99.0)])


# ``eva_step_mfu_pct`` was the fifth until PR 66: the cell is on ``step_mfu_pct``'s list
NEW_READERS = ("eva_flash_ms", "eva_flash_roofline", "xla_mixer_pool_ms", "eva_multibyte_nll")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "quorum_ms", "commit_vote_ms", "step_device_ms", "device_idle_pct", "peak_hbm_gb",
          "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms",
          "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_and_counter_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("eva_flash_ms") == pytest.approx(4 * 75.0)
    count, s = cell.architecture.eva_flops, sources["shapes"]
    assert read("eva_flash_roofline") == pytest.approx(flops.roofline_pct(*count.flash_step(s, 1, SEQ), 0.300, "TPU v5 lite")["pct"])
    assert 0 < read("eva_flash_roofline") < 100
    busy = 1.2 + 0.300 + 0.001  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    assert 0 < read("step_mfu_pct") < 100
    # no experts and no launch called ``flash_fwd``: the two other folded readers find nothing
    assert read("moe_gmm_roofline") is None and read("flash_roofline") is None
    # the further slices' mean loss over the window's events (the one before the window is not in it)
    assert read("eva_multibyte_nll") == pytest.approx(6.0) and abs(read("eva_multibyte_nll") - math.log(320)) < 0.5
    # no other architecture's kernel reader matches these names, and theirs find nothing here
    for theirs in ("flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "swa_flash_ms", "moe_gmm_ms", "mtp_nll"):
        assert read(theirs) is None, theirs


def _scoped_ops():
    """One step of 100 ms a device plane's way (``device_scopes.Op``), twice:
    the pooling forward, rematerialised and backward (2 + 2 + 5 ms), the glue
    around it, a kernel under the glue, the head, an operation under no scope."""
    rows = [
        ("%fusion.1 = f32[8] fusion(%p)", 0, 2, "jit(_step)/jvp(tpuft.layers)/checkpoint/tpuft.mixer_glue/tpuft.mixer_pool/reduce_sum:"),
        ("%fusion.2 = f32[8] fusion(%p)", 2, 4, "jit(_step)/transpose(jvp(tpuft.layers))/rematted_computation/tpuft.mixer_glue/tpuft.mixer_pool/exp:"),
        ("%fusion.3 = f32[8] fusion(%p)", 4, 9, "jit(_step)/transpose(jvp(tpuft.layers))/tpuft.mixer_glue/tpuft.mixer_pool/mul:"),
        ("%fusion.4 = f32[8] fusion(%p)", 9, 12, "jit(_step)/jvp(tpuft.layers)/checkpoint/tpuft.mixer_glue/concatenate:"),
        ("%eva_fwd.5 = bf16[8] custom-call(%q)", 12, 20, "jit(_step)/jvp(tpuft.layers)/checkpoint/tpuft.mixer_glue/eva_fwd/pallas_call:"),
        ("%fusion.6 = f32[8] fusion(%p)", 20, 30, "jit(_step)/jvp(tpuft.head)/dot_general:"),
        ("%copy.7 = f32[8] copy(%p)", 30, 31, ""),
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


def test_the_poolings_own_time_is_its_scopes_and_the_parts_still_tile_the_step(monkeypatch):
    """``xla_mixer_pool_ms`` reads ``tpuft.mixer_pool`` where it is INNERMOST:
    the glue around it stays the glue's, a kernel under the glue (found by its
    path: ``eva_`` is no name ``device_scopes`` lists) is the kernels'.  With
    the part, the parts, the unscoped rest and the kernels' own time are the
    step's device time."""
    planes = {0: _scoped_ops()}
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    steps = [dict(t_enter=1.0, t_exit=1.1), dict(t_enter=1.1, t_exit=1.2)]
    sources = dict(trace=dict(per_device={0: dict(ops=[])}, offset=0.0, traced_steps=[steps]), replicas=1, groups_share_chip=False)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("xla_mixer_pool_ms") == pytest.approx(9.0) and read("xla_mixer_glue_ms") == pytest.approx(3.0)
    assert read("xla_head_ms") == pytest.approx(10.0) and read("xla_unscoped_ms") == pytest.approx(1.0)
    assert read("step_remat_ms") == pytest.approx(2.0)
    parts = ("xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_mixer_pool_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms",
             "xla_layer_scan_ms", "optimizer_ms")
    kernels = device_scopes.own_ms_per_step(sources, lambda op: op["kernel"])
    assert kernels == pytest.approx(8.0)
    assert sum(read(name) or 0.0 for name in parts) + read("xla_unscoped_ms") + kernels == pytest.approx(31.0)
    # a program with scopes and no pooling (any other cell): None, not 0
    planes[0] = [op for op in planes[0] if op["part"] != "mixer_pool"]
    device_scopes._CUT.clear()
    assert read("xla_mixer_pool_ms") is None and read("xla_head_ms") == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("higher" if name == "eva_flash_roofline" else "lower")
    assert entry["layer"] == ("kernels" if name.startswith("eva_flash") else "compiled step")
    assert entry["source"] == ("program_counter" if name == "eva_multibyte_nll" else "device_trace")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name, monkeypatch):
    """The parent commit has no such architecture, no ``tpuft.mixer_pool``, no
    ``eva_*`` kernel and no ``multibyte_nll`` in its events: the reader returns
    None, never raises, and the metric is left out."""
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%flash_fwd.1 = bf16[2048,4096] custom-call(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    read = spec.load_metric(name, BENCH_DIR).read
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "trinitymini-ws1-seq16k", "joyaiflash-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
        assert read(dict(sources, flight=[[]])) is None
    # this architecture's shapes over a trace without its kernels and events without the field
    assert read(_trace_sources(cell, ops, old_events)) is None
    if name == "eva_multibyte_nll":
        assert read(_made_trace(cell, further=False)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="evabyte-6.5b-1x1", traffic="ws1-seq32k", chips=1)
    assert len(entry["why"]) <= 200 and "32,768" in entry["why"] and "a fifth" in entry["why"] and "2,560" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    # what this model has no part of stays without it: another architecture's
    # kernels and counting, the experts' readers, another regime's end-to-end metric
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("flash_", "kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_", "moe_", "latent_", "mtp_")), name
            assert moved.get(name, "tokens_per_s_per_chip") == "tokens_per_s_per_chip", name
    traffic = spec.load_cell(CELL).traffic
    assert (traffic["replicas"], traffic["seq_len"], traffic["sequences_per_chip"]) == (1, SEQ, 1)
    assert (traffic["warmup_steps"], traffic["trace_steps"], traffic["kill"], traffic["quantize_outer"]) == (5, 8, None, False)
    assert "byte" in traffic["who"]


def test_the_yardsticks_k_lies_between_its_two_readings(cell):
    arch = cell.architecture
    assert arch.READ_CONTROL_HIGH < arch.COARSE_RATIO_K < arch.READ_SOUND_LOW <= arch.READ_SOUND_HIGH
    # room on both sides: the worst sound seed and the nearest control each a quarter away at the least
    assert arch.READ_SOUND_LOW / arch.COARSE_RATIO_K > 1.25 and arch.COARSE_RATIO_K / arch.READ_CONTROL_HIGH > 1.25


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "eva_multibyte_nll"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary with the further slices' loss in the flight events, the
    float32 reference with the tie of ``loss`` to ``apply`` while the eight
    slices are in the objective, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000052", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"eva_flash_ms", "eva_flash_roofline", "step_mfu_pct", "xla_mixer_pool_ms", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4 and checks["loss_tie"] <= 2e-5
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.3492, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["seq_len"] % toy["config"]["window_size"] == 0 and toy["seq_len"] // toy["config"]["window_size"] == 4
