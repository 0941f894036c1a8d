"""The configuration ``nemotron-3-nano-30b-a3b-ep8-1x1``, its architecture
file, its counting of operations and bytes, its readers and the CPU rehearsal
of the cell ``nemotron3nano-ws1-seq16k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import flops, spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "nemotron3nano-ws1-seq16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "expand", "num_experts_per_tok",
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
    assert sorted(config["reduced"]) == ["hybrid_override_pattern", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["moe_intermediate_size"], config["moe_shared_expert_intermediate_size"]) == (2688, 1856, 3712)
    assert (config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"], config["n_groups"]) == (64, 64, 128, 8)
    assert (config["conv_kernel"], config["chunk_size"], config["layer_norm_epsilon"]) == (4, 128, 1e-5)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (32, 2, 128)
    assert (config["num_experts_per_tok"], config["routed_scaling_factor"], config["mlp_hidden_act"]) == (6, 2.5, "relu2")
    # the pattern is the published string's first nine characters, every kind present
    published = config["published"]["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"] == published[:9] == "MEMEM*EME" and len(published) == 52
    assert config["num_hidden_layers"] == 9 and {c: published.count(c) for c in "ME*"} == {"M": 23, "E": 23, "*": 6}
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == config["published"]["n_routed_experts"] == 128
    assert config["experts_held"] == [0, config["n_routed_experts"]] == [0, 16]
    # the floors: every kind and over four layers, 8 experts, an eighth of the vocabulary
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("learning_rate", "optimizer", "bias_update_rate", "bias_update", "balance_loss_weight", "balance_loss",
                "position_encoding", "ssm_init", "gated_norm", "residual_stream", "router", "expert_form", "weights"):
        assert key in config["assumed"], key
    assert "INFERENCE" in config["assumed"]["position_encoding"]
    assert "8 chips share" in config["stands_for"] and "768 tokens" in config["stands_for"] and "6,144" in config["stands_for"]
    assert config["parameters_here"].startswith("986.25 M")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 986_254_848 and config["parameters_here"].startswith("986.25 M")
    assert arch.vocab(config) == 16_384 and arch.KERNEL_PATHS == {"ssd+flash"}
    s = arch.shapes(config)
    assert (s["n_ssm"], s["n_moe"], s["n_attention"]) == (4, 4, 1)
    count = arch.ssm_flops
    other = spec.load_cell("keye2-ws1-seq16k")
    assert count.is_mine(s) and not count.is_mine(other.architecture.shapes(other.config))
    # ISSUE 35: a token's matmul parameters: a state-space layer 38.7 M, the attention layer 23.4 M, an
    # expert layer 20.0 M shared + 0.34 M router + 6 x 9.98 M x 16/128 = 7.5 M routed, the head 44 M
    ssm, attention, moe, head = 2688 * 10304 + 4096 * 2688, 2 * 2688 * 4096 + 2 * 2688 * 256, 0, 2688 * 16384
    moe = 2688 * 128 + 2 * 2688 * 3712 + 6 * 16 / 128 * 2 * 2688 * 1856
    assert count.matmul_params_touched(s) == 4 * ssm + attention + 4 * moe + head
    assert (ssm, attention, head) == (38_707_200, 23_396_352, 44_040_192) and moe == pytest.approx(27.78e6, rel=1e-3)


def test_counting_by_hand_at_toy_widths(cell):
    """``ssm_flops`` against a count by hand: 2 heads of 4 in 1 group, a state
    of 8, chunks of 4, 8 positions; one layer of each kind."""
    count = cell.architecture.ssm_flops
    s = dict(dim=16, n_ssm=1, n_attention=1, n_moe=1, ssm_heads=2, ssm_head_dim=4, ssm_state=8, ssm_groups=1, chunk=4,
             n_heads=4, n_kv_heads=2, head_dim=8, expert_hidden=12, shared_hidden=20, router_experts=8, experts_held=2,
             top_k=2, vocab_size=32)
    # forward, a token: C B^T over the 2.5 tokens of the chunk before it (8 a pair, once a group: 2 x 8 x 2.5),
    # the masked product with dt x (2 x 4 x 2.5 a head), the state's read-out and update (2 x 8 x 4 each a head)
    forward = 2 * 8 * 2.5 + 2 * (2 * 4 * 2.5 + 2 * 2 * 8 * 4)
    operations, nbytes = count.ssd_step(s, rows=1.0, seq=8)
    assert operations == 3 * forward * 8
    operands = (2 * 4 + 2 * 8) * 2 + 2 * 2 * 4  # dt x, B and C in bfloat16; the log decay twice in float32
    states = 2 * 4 * 8 * 4 / 4  # a chunk's starting state in float32, a token's share
    assert nbytes == 8 * ((operands + 2 * 4 * 2 + states) + (operands + states + 2 * 4 * 2 + operands))
    # attention: six products of 2 S S D a query head, halved; q, o (4 heads) and k, v (2) of 8
    operations, nbytes = count.flash_step(s, rows=1.0, seq=8)
    assert operations == 6 * 2 * 8 * 8 * 8 * 4 / 2
    assert nbytes == 8 * 8 * ((2 * 4 + 2 * 2) + (4 * 4 + 4 * 2)) * 2
    # experts: TWO products forward and four backward a row; two matrices an expert
    operations, nbytes = count.gmm_step(s, rows_here=10.0)
    assert operations == 6 * 2 * 16 * 12 * 10
    assert nbytes == 3 * 2 * (2 * 16 * 12) * 2 + 3 * 10 * (2 * 16 + 2 * 12) * 2
    params = (16 * (2 * 8 + 2 * 8 + 2) + 8 * 16) + (2 * 16 * 32 + 2 * 16 * 16) + (16 * 8 + 2 * 16 * 20 + 2 * 2 / 8 * 2 * 16 * 12) + 16 * 32
    assert count.matmul_params_touched(s) == params
    assert count.train_flops_per_token(s, 8) == 6 * params + (count.ssd_step(s, 1.0, 8)[0] + count.flash_step(s, 1.0, 8)[0]) / 8


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=3.0), dict(t_enter=3.0, t_exit=5.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=SEQ, rows_per_replica=1,
        tokens_per_step_per_replica=SEQ, device_kind="TPU v5 lite",
    )


def _made_trace(cell):
    """Two steps as the chip's trace names them: eight ``ssd_fwd`` (four
    layers, each again in the backward pass) and four ``ssd_bwd``, flash's
    three, the grouped products, and operations that only MENTION a kernel."""
    call = "%{} = bf16[1,64,16384,64] custom-call(bf16[1,64,16384,64] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 2.0 * step
        ops.append(("%fusion.9 = bf16[16384,2688] fusion(%p)", at, 0.5))
        ops += [(call.format(f"ssd_fwd.{16 + i}"), at + 0.50 + 0.002 * i, 0.002) for i in range(8)]
        ops += [(call.format(f"ssd_bwd.{8 + i}"), at + 0.52 + 0.004 * i, 0.004) for i in range(4)]
        ops += [
            (call.format("flash_fwd.2"), at + 0.54, 0.040), (call.format("flash_dq.2"), at + 0.58, 0.030),
            (call.format("flash_dkv.2"), at + 0.61, 0.045),
            (call.format("jvp_jit_gmm__.4"), at + 0.66, 0.100),
            (call.format("transpose_jvp_jit_tgmm___.9"), at + 0.76, 0.060),
            ("%get-tuple-element.3 = f32[1,64,128,64,128] get-tuple-element(%ssd_fwd.16), index=1", at + 0.82, 0.001),
            ("%copy.8 = bf16[1,32,16384,128] copy(%flash_fwd.2)", at + 0.821, 0.001),
        ]
    event = lambda t, rows: dict(  # noqa: E731
        name="MOE_ROUTE", t=t, rows_here=[rows] * 4, load_max=[1.5 * rows / 16] * 4, load_mean=[rows / 16] * 4
    )
    return _trace_sources(cell, ops, [event(2.9, 12288.0), event(4.9, 13312.0), event(0.5, 9.0)])


# ``ssm_flash_roofline``, ``ssm_moe_gmm_roofline`` and ``ssm_step_mfu_pct`` were three more until PR 66: the cell
# is on the three folded readers' lists
NEW_READERS = ("ssd_fwd_ms", "ssd_bwd_ms", "ssd_roofline")
JOINED = ("tokens_per_s_per_chip", "step_mfu_pct", "flash_roofline", "moe_gmm_roofline", "step_device_ms", "device_idle_pct", "peak_hbm_gb", "quorum_ms", "commit_vote_ms",
          "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms", "moe_gmm_ms", "moe_rows_here_per_step", "moe_load_max_over_mean")


def test_kernel_readers_on_a_made_trace(cell):
    sources = _made_trace(cell)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("ssd_fwd_ms") == pytest.approx(16.0) and read("ssd_bwd_ms") == pytest.approx(16.0)
    assert read("flash_fwd_ms") == pytest.approx(40.0) and read("flash_dq_ms") == pytest.approx(30.0)
    assert read("flash_dkv_ms") == pytest.approx(45.0) and read("moe_gmm_ms") == pytest.approx(160.0)
    count, s = cell.architecture.ssm_flops, sources["shapes"]
    for name, need, seconds in (
        ("ssd_roofline", count.ssd_step(s, 1, SEQ), 0.032),
        ("flash_roofline", count.flash_step(s, 1, SEQ), 0.115),
        ("moe_gmm_roofline", count.gmm_step(s, 12800.0), 0.160),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    # the scan is bound by memory at these widths, attention by compute
    assert flops.roofline_pct(*count.ssd_step(s, 1, SEQ), 1.0, "TPU v5 lite")["bound"] == "memory"
    assert flops.roofline_pct(*count.flash_step(s, 1, SEQ), 1.0, "TPU v5 lite")["bound"] == "compute"
    assert read("moe_rows_here_per_step") == pytest.approx(4 * 12800.0)
    assert read("moe_load_max_over_mean") == pytest.approx(1.5)
    busy = 0.5 + 0.016 + 0.016 + 0.115 + 0.160 + 0.002  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(100 * SEQ / busy * count.train_flops_per_token(s, SEQ) / 197e12)
    # the readers of another architecture's shapes find nothing here
    for theirs in ("kda_roofline", "dsa_attn_roofline", "swa_flash_roofline", "gdn_roofline", "eva_flash_roofline"):
        assert read(theirs) is None, theirs


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_meta_is_its_entry_and_it_lists_this_cell_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    meta = spec.load_metric(name, BENCH_DIR).META
    assert meta == {k: entry[k] for k in ("source", "layer", "unit", "moves")}
    assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s_per_chip"


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such kernel and no such architecture: the
    reader returns None, never raises, and the metric is left out."""
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 3.0, 0.1)]
    old_events = [dict(name="MOE_ROUTE", t=2.9, rows_here=[2048.0], load_max=[160.0], load_mean=[128.0])]
    for other in ("mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "keye2-ws1-seq16k"):
        theirs = spec.load_cell(other)
        sources = _trace_sources(cell, ops, old_events)
        sources["shapes"] = theirs.architecture.shapes(theirs.config)
        read = spec.load_metric(name, BENCH_DIR).read
        assert read(sources) is None
        assert read(dict(sources, trace=None)) is None
    # this architecture's shapes over a trace without its kernels: still nothing for a kernel's reader
    assert spec.load_metric(name, BENCH_DIR).read(_trace_sources(cell, ops, old_events)) is None


def test_the_cell_and_the_lists_it_joined():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config="nemotron-3-nano-30b-a3b-ep8-1x1", traffic="ws1-seq16k", chips=1)
    assert len(entry["why"]) <= 200 and "heads whole" in entry["why"] and "an eighth" in entry["why"]
    # what the test means, never a place or a count: a later PR's cell,
    # configuration or reader may stand anywhere and edits nothing here
    assert entry["config"] in [c["name"] for c in bench["configs"]]
    listed = {m["name"]: m.get("workloads") for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED + NEW_READERS:
        assert CELL in listed[name], name
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
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the selection bias in its slot, the step's summary in the flight events,
    the float32 reference with its token-by-token recurrence, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000043", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"ssd_fwd_ms", "ssd_roofline", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.2422, abs=1e-3)
