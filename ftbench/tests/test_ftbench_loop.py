"""The configuration ``ouro-2.6b-1x1``, its architecture file, its counting of
operations and bytes, its readers and the CPU rehearsal of the cell
``ouro2.6b-ws1-seq16k``.  No number here is a device's."""

import json
import math
import os

import pytest

from ftbench import device_scopes, flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "ouro2.6b-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width, the heads, the vocabulary, the passes
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
          "total_ut_steps")
SEQ = 16384


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_one_cut_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    # the ONE cut is the depth; ``layer_types`` is a type a layer and follows it, named as Trinity's is
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"]) == ["layer_types", "num_hidden_layers"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["intermediate_size"], config["num_attention_heads"], config["num_key_value_heads"]) == (2048, 5632, 16, 16)
    assert (config["head_dim"], config["vocab_size"], config["total_ut_steps"], config["early_exit_threshold"]) == (128, 49152, 4, 1)
    assert (config["rope_theta"], config["rms_norm_eps"], config["tie_word_embeddings"], config["model_type"]) == (1000000, 1e-6, False, "ouro")
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (8, 48)
    assert config["layer_types"] == ["full_attention"] * 8 and config["max_window_layers"] == 48
    for key in ("learning_rate", "entropy_beta", "optimizer", "origin", "torch_dtype", "loop", "layer", "gate", "objective",
                "precision", "gradient_sum", "remat", "weights", "batch", "kernels", "model_code"):
        assert key in config["assumed"], key
    assert (config["assumed"]["learning_rate"], config["assumed"]["entropy_beta"]) == (3e-4, 0.05)
    assert "gate-only" in config["assumed"]["objective"] and "NOT part of the step" in config["assumed"]["objective"]
    assert "six pipeline stages of eight" in config["stands_for"] and "19.7 %" in config["stands_for"] and "3.9 %" in config["stands_for"]
    assert config["parameters_here"].startswith("612,438,017")
    assert config["layout"] == dict(chips_per_group=1, groups_share_chip=False, fsdp=1)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k) != v} == {"num_hidden_layers", "layer_types"}
        assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == len(row["config"]["layer_types"])
        assert set(row["config"]["layer_types"]) == {"full_attention"}


@pytest.mark.parametrize(
    "key,value,why",
    [
        ("model_type", "llama", "model_type ouro"),
        ("early_exit_threshold", 0.5, "no early exit"),
        ("num_key_value_heads", 4, "its own k and v"),
        ("layer_types", ["full_attention"] * 7 + ["sliding_attention"], "every layer is full_attention"),
        ("tie_word_embeddings", True, "untied head"),
    ],
)
def test_the_adapter_refuses_a_configuration_it_was_not_built_for(cell, key, value, why):
    with pytest.raises(ValueError, match=why):
        cell.architecture.model(dict(cell.config, **{key: value}))


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 612_438_017  # ONE set of layer leaves: no leaf a pass
    assert arch.vocab(config) == 49152 and arch.KERNEL_PATHS == {"flash"}
    s = arch.shapes(config)
    assert (s["n_layers"], s["n_heads"], s["n_kv_heads"], s["head_dim"], s["loop_passes"]) == (8, 16, 16, 128, 4)
    count = arch.looped_flops
    other = spec.load_cell("mistral7b-ws1-steady")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config)) and not count.is_mine(None)
    # ISSUE 59: a layer's matrices 51.38 M, the head 100.66 M, each once a PASS: 2,046.8 M a token
    layer, head = 4 * 2048 * 2048 + 3 * 2048 * 5632, 2048 * 49152
    assert count.matmul_params_touched(s) == 4 * (8 * layer + head) == 2_046_820_352 and layer == 51_380_224
    assert 4 * head / count.matmul_params_touched(s) == pytest.approx(0.197, abs=1e-3)  # the head's 19.7 %
    assert 6 * count.matmul_params_touched(s) == pytest.approx(12.28e9, rel=1e-3)
    # attention over the LIVE causal pairs of 32 applications: 6 x 16,384 x 2,048 x 32 a token
    operations, nbytes = count.flash_step(s, 1.0, SEQ)
    assert operations / SEQ == 6 * SEQ * 2048 * 32 and operations / SEQ == pytest.approx(6.44e9, rel=1e-3)
    assert operations == flops.flash_step_flops(dict(s, n_layers=32), 1.0, SEQ)
    assert nbytes == 32 * 12 * SEQ * 2048 * 2  # q, k, v, o forward and eight more backward, bfloat16, MHA
    assert flops.roofline_pct(operations, nbytes, 1.0, "TPU v5 lite")["bound"] == "compute"
    per_token = count.train_flops_per_token(s, SEQ)
    assert per_token == 6 * 2_046_820_352 + 6 * SEQ * 2048 * 32 and per_token == pytest.approx(18.72e9, rel=1e-3)
    # a step: 307 TFLOP, 1.56 s at the chip's peak; the full square would credit twice the attention done
    assert per_token * SEQ == pytest.approx(306.8e12, rel=1e-3) and per_token * SEQ / 197e12 == pytest.approx(1.557, abs=2e-3)
    assert flops.train_flops_per_token(dict(s, n_layers=32), SEQ) - 6 * flops.matmul_params(dict(s, n_layers=32)) == 2 * operations / SEQ


def test_counting_by_hand_at_toy_widths(cell):
    """``looped_flops`` against a count by hand: 2 heads of 4, 3 layers, 2
    passes, a vocabulary of 5."""
    count = cell.architecture.looped_flops
    s = dict(dim=8, n_layers=3, n_heads=2, n_kv_heads=2, head_dim=4, ffn_hidden=12, vocab_size=5, loop_passes=2)
    assert count.matmul_params_touched(s) == 2 * (3 * (4 * 8 * 8 + 3 * 8 * 12) + 8 * 5)
    operations, nbytes = count.flash_step(s, rows=2.0, seq=24)
    # six products of 2 S^2 d a head, halved by causality, two rows, 3 x 2 applications
    assert operations == 6 * (6 * 2 * 24 * 24 * 4 * 0.5 * 2 * 2)
    assert nbytes == 6 * (12 * 2 * 24 * 8 * 2)
    assert count.train_flops_per_token(s, 24) == 6 * count.matmul_params_touched(s) + count.flash_step(s, 1.0, 24)[0] / 24


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=4.0), dict(t_enter=4.0, t_exit=7.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell, looped=True):
    """Two steps as the chip's trace names them: 32 applications' ``flash_*``
    (a forward kernel of 9 ms, run AGAIN in the rematerialised layer, a ``dq``
    of 14, a ``dkv`` of 16) and operations that only MENTION a kernel; the
    step's events with every pass's loss and the exit distribution (``looped``
    False: as a program without the fields)."""
    call = "%{} = bf16[1,16,16384,128] custom-call(bf16[1,16,16384,128] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 3.0 * step
        ops.append(("%fusion.9 = bf16[16384,2048] fusion(%p)", at, 1.3))
        for n in range(32):
            t = at + 1.3 + 0.05 * n
            ops += [
                (call.format(f"flash_fwd.{2 + n}"), t, 0.009), (call.format(f"flash_fwd.{40 + n}"), t + 0.009, 0.009),
                (call.format(f"flash_dq.{2 + n}"), t + 0.018, 0.014), (call.format(f"flash_dkv.{2 + n}"), t + 0.032, 0.016),
            ]
        ops.append(("%copy.9 = bf16[1,16,16384,128] copy(%flash_fwd.2)", at + 2.95, 0.001))
    fields = lambda first, entropy: dict(  # noqa: E731
        pass_nll=[first, first - 0.1, first - 0.2, first - 0.3], exit_p=[0.5, 0.25, 0.125, 0.125], exit_entropy=entropy,
    )
    event = lambda t, first, entropy: dict(name="MOE_ROUTE", t=t, **(fields(first, entropy) if looped else {}))  # noqa: E731
    return _trace_sources(cell, ops, [event(3.9, 10.9, 1.15), event(6.9, 10.5, 1.05), event(0.5, 99.0, 9.0)])


# ``loop_step_mfu_pct`` and ``loop_flash_roofline`` were two more until PR 66: the cell is on the lists of
# ``step_mfu_pct`` and ``flash_roofline``
NEW_READERS = ("xla_loop_gate_ms", "loop_first_pass_nll", "loop_exit_entropy")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "step_device_ms", "device_idle_pct", "peak_hbm_gb", "quorum_ms", "commit_vote_ms",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_ffn_ms",
          "xla_stream_ms", "xla_head_ms", "xla_layer_scan_ms", "optimizer_ms", "step_remat_ms", "xla_unscoped_ms")


def test_kernel_and_counter_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    # the accepted readers of the three kernels find them in this cell; the second forward run counts as TIME
    assert read("flash_fwd_ms") == pytest.approx(32 * 18.0) and read("flash_dq_ms") == pytest.approx(32 * 14.0)
    assert read("flash_dkv_ms") == pytest.approx(32 * 16.0)
    count, s = cell.architecture.looped_flops, sources["shapes"]
    kernels = 32 * 0.048
    assert read("flash_roofline") == pytest.approx(flops.roofline_pct(*count.flash_step(s, 1, SEQ), kernels, "TPU v5 lite")["pct"])
    # recomputed work uncredited: the forward kernel run twice LOWERS the share, it adds no operation
    assert 0 < read("flash_roofline") < 100
    busy = 1.3 + kernels + 0.001  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    assert 0 < read("step_mfu_pct") < 100
    # the window's events (the one before the window is not in it)
    assert read("loop_first_pass_nll") == pytest.approx(10.7) and abs(read("loop_first_pass_nll") - math.log(49152)) < 0.5
    assert read("loop_exit_entropy") == pytest.approx(1.1) and 0 < read("loop_exit_entropy") < math.log(4)
    # another architecture's counting finds nothing here
    for theirs in ("moe_gmm_roofline", "swa_flash_roofline", "eva_flash_roofline", "gdn_roofline", "eva_multibyte_nll", "mtp_nll",
                   "moe_gmm_ms"):
        assert read(theirs) is None, theirs


def _scoped_ops():
    """One step of 100 ms a device plane's way (``device_scopes.Op``), twice:
    the gate forward and backward (2 + 5 ms), a block of the head forward,
    rematerialised and backward under ``head``, a kernel, an operation under
    no scope."""
    rows = [
        ("%fusion.1 = f32[8] fusion(%p)", 0, 2, "jit(_step)/jvp(tpuft.loop_gate)/log1p:"),
        ("%fusion.2 = f32[8] fusion(%p)", 2, 7, "jit(_step)/transpose(jvp(tpuft.loop_gate))/mul:"),
        ("%fusion.3 = f32[8] fusion(%p)", 7, 11, "jit(_step)/jvp(tpuft.head)/while/body/checkpoint/dot_general:"),
        ("%fusion.4 = f32[8] fusion(%p)", 11, 14, "jit(_step)/transpose(jvp(tpuft.head))/while/body/rematted_computation/dot_general:"),
        ("%fusion.5 = f32[8] fusion(%p)", 14, 20, "jit(_step)/transpose(jvp(tpuft.head))/while/body/dot_general:"),
        ("%flash_fwd.6 = bf16[8] custom-call(%q)", 20, 28, "jit(_step)/jvp(tpuft.layers)/while/body/while/body/checkpoint/tpuft.mixer_glue/flash_fwd/pallas_call:"),
        ("%copy.7 = f32[8] copy(%p)", 28, 29, ""),
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


def test_the_gates_own_time_is_its_scopes_and_the_parts_still_tile_the_step(monkeypatch):
    """``xla_loop_gate_ms`` reads ``tpuft.loop_gate`` where it is INNERMOST:
    the heads' products stay ``head``'s, forward, rematerialised and
    backward.  With the part, the parts, the unscoped rest and the kernels'
    own time are the step's device time."""
    planes = {0: _scoped_ops()}
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    device_scopes._CUT.clear()
    steps = [dict(t_enter=1.0, t_exit=1.1), dict(t_enter=1.1, t_exit=1.2)]
    sources = dict(trace=dict(per_device={0: dict(ops=[])}, offset=0.0, traced_steps=[steps]), replicas=1, groups_share_chip=False)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("xla_loop_gate_ms") == pytest.approx(7.0) and read("xla_head_ms") == pytest.approx(13.0)
    assert read("xla_unscoped_ms") == pytest.approx(1.0) and read("step_remat_ms") == pytest.approx(3.0)
    parts = ("xla_mixer_proj_ms", "xla_mixer_glue_ms", "xla_mixer_pool_ms", "xla_ffn_ms", "xla_stream_ms", "xla_head_ms",
             "xla_mtp_ms", "xla_loop_gate_ms", "xla_layer_scan_ms", "optimizer_ms")
    kernels = device_scopes.own_ms_per_step(sources, lambda op: op["kernel"])
    assert kernels == pytest.approx(8.0)
    assert sum(read(name) or 0.0 for name in parts) + read("xla_unscoped_ms") + kernels == pytest.approx(29.0)
    # a program with scopes and no gate (any other cell): None, not 0
    planes[0] = [op for op in planes[0] if op["part"] != "loop_gate"]
    device_scopes._CUT.clear()
    assert read("xla_loop_gate_ms") is None and read("xla_head_ms") == pytest.approx(13.0)
    device_scopes._CUT.clear()


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"
    assert entry["better"] == ("lower" if name in ("xla_loop_gate_ms", "loop_first_pass_nll") else "higher")
    assert entry["layer"] == "compiled step"
    assert entry["source"] == ("program_counter" if name in ("loop_first_pass_nll", "loop_exit_entropy") else "device_trace")


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name, monkeypatch):
    """The parent commit has no such architecture, no ``tpuft.loop_gate`` and
    no ``pass_nll`` in its events: the reader returns None, never raises, and
    the metric is left out."""
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    device_scopes._CUT.clear()
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%flash_fwd.1 = bf16[2048,4096] custom-call(%p)", 4.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=3.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    read = spec.load_metric(name, BENCH_DIR).read
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "trinitymini-ws1-seq16k", "evabyte-ws1-seq32k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        assert read(sources) is None  # Mistral's flash kernels under Mistral's shapes are not this reader's
        assert read(dict(sources, trace=None)) is None
        assert read(dict(sources, flight=[[]])) is None
    # this architecture's shapes over events without the fields, and over no trace at all
    assert read(dict(_trace_sources(cell, ops, old_events), trace=None)) is None
    if name in ("loop_first_pass_nll", "loop_exit_entropy"):
        assert read(_made_trace(cell, looped=False)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="ouro-2.6b-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "16,384" in entry["why"] and "19.7 %" in entry["why"] and "ONE set" in entry["why"]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert config["file"] == "ftbench/configs/ouro-2.6b-1x1.json"
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert CELL in listed[name], name
    for name in NEW_READERS:
        assert listed[name] == [CELL], name
    assert len(bench["per_layer"]) <= 128
    # what this model has no part of stays without it: another architecture's
    # kernels and counting, the experts' readers, another regime's end-to-end metric
    moved = {m["name"]: m.get("moves") for m in bench["per_layer"]}
    for name, cells in listed.items():
        if cells and CELL in cells:
            assert not name.startswith(("kda_", "mla_", "ling_", "dsa_", "ssd_", "ssm_", "swa_", "moe_", "latent_", "mtp_", "eva_", "gdn_")), name
            assert name not in ("moe_gmm_roofline", "xla_mixer_pool_ms", "xla_mtp_ms"), name
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
        (1, {"quorum_ms", "commit_vote_ms", "loop_first_pass_nll", "loop_exit_entropy"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the step's summary with every pass's loss and the exit distribution in the
    flight events, the float32 reference with the tie of ``loss`` to ``apply``
    while the four heads and the gate are in the objective, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000059", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"step_mfu_pct", "flash_roofline", "xla_loop_gate_ms", "flash_fwd_ms", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4 and checks["loss_tie"] <= 2e-5
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.3954, abs=1e-3)
    toy = spec.load_cell(CELL).architecture.TOY
    assert toy["config"]["num_key_value_heads"] == toy["config"]["num_attention_heads"]
    assert toy["config"]["hidden_size"] == toy["config"]["num_attention_heads"] * toy["config"]["head_dim"]
