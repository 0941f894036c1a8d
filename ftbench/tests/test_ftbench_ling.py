"""The configuration ``ling-3.0-flash-ep32-1x1``, its architecture file, its
counting of operations and bytes, its readers and the CPU rehearsal of the
cell ``ling3flash-ws1-seq8k``.  No number here is a device's."""

import json
import os

import pytest

from ftbench import spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "ftbench")
CELL = "ling3flash-ws1-seq8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the keys of the source that a cut may not touch: every width
WIDTHS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim", "kv_lora_rank", "qk_head_dim",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rotary_dim", "num_experts_per_tok",
    "short_conv_kernel_size", "n_group", "topk_group",
)


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_source_with_the_cuts_it_lists(cell):
    config = cell.config
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(config["published"])
    assert not set(config["reduced"]) & set(WIDTHS)
    assert (config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]) == (2560, 6144, 768)
    assert (config["num_attention_heads"], config["head_dim"], config["kv_lora_rank"]) == (32, 128, 512)
    assert (config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]) == (128, 64, 128)
    assert (config["num_experts_per_tok"], config["n_group"], config["topk_group"]) == (8, 8, 4)
    # the router keeps its width; the key that counts experts says how many are held
    assert config["router_experts"] == config["published"]["num_experts"] == 512
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 16]
    # the floors: a whole period and four layers after the dense one, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= config["layer_group_size"]
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for key in ("bias_update_rate", "balance_loss_weight", "learning_rate", "optimizer", "use_mla_nope",
                "max_window_layers", "group_norm_size", "linear_silu"):
        assert key in config["assumed"], key
    assert "32 chips" in config["stands_for"] and "8 chips" in config["stands_for"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == set(config["reduced"])
        assert all(config["published"][k] == row["config"][k] for k in config["reduced"])


def test_layer_pattern_kept_is_one_dense_layer_and_a_whole_period(cell):
    kinds = cell.architecture.reference.layer_kinds(cell.config)
    assert kinds == [("kda", "dense")] + [("kda", "moe")] * 4 + [("mla", "moe"), ("kda", "moe")]
    model = cell.architecture.model(cell.config)
    assert [(k[0], k[1], d) for k, d in model.config.groups()] == [
        ("kda", "dense", 1), ("kda", "moe", 4), ("mla", "moe", 1), ("kda", "moe", 1)
    ]
    assert model.config.experts_held == (0, 16) and model.config.num_experts == 512


def test_counting_of_parameters_and_operations(cell):
    arch, config = cell.architecture, cell.config
    assert arch.num_params(config) == 1_105_151_936  # ISSUE 29: 1,105.2 M, 8.84 GB at 8 bytes
    assert arch.vocab(config) == 19_648 and arch.KERNEL_PATHS == {"kda+flash"}
    s = arch.shapes(config)
    count = arch.ling_flops
    # what ONE token touches: 6 KDA mixers 316 M, MLA 32 M, dense 47 M, routers and
    # shared experts 43 M, the experts held 9 M (0.25 of a token's 8 choices), head 50 M
    assert count.matmul_params_touched(s) == pytest.approx(497.1e6, rel=1e-3)
    routed = 8 * 16 / 512 * 3 * 2560 * 768 * 6
    assert routed == pytest.approx(8.8e6, rel=1e-2)
    kda_flops, kda_bytes = count.kda_step(s, 1, 8192)
    assert kda_flops == 6 * 3 * 6 * 128 * 128 * 8192 * 32
    assert kda_bytes == 6 * 2 * 8192 * 32 * 5 * 128 * 2
    mla_flops, _ = count.mla_flash_step(s, 1, 8192)
    assert mla_flops / 3 / 8192 == pytest.approx(84e6, rel=2e-2)  # ISSUE 29: 84 MFLOP a token forward
    gmm_flops, gmm_bytes = count.gmm_step(s, 2048.0)
    assert gmm_flops == 6 * 9 * 2 * 2560 * 768 * 2048
    assert gmm_bytes > 6 * 3 * 16 * 3 * 2560 * 768 * 2  # at least the held weights three times
    per_token = count.train_flops_per_token(s, 8192)
    assert 6 * 497.1e6 < per_token < 6 * 497.1e6 + 0.4e9


def _trace_sources(cell, ops, flight=None):
    steps = [dict(t_enter=1.0, t_exit=1.5), dict(t_enter=1.5, t_exit=2.0)]
    return dict(
        trace=dict(per_device={0: dict(ops=ops)}, offset=0.0, traced_steps=[steps]),
        window=[steps], flight=[flight or []], replicas=1, groups_share_chip=False, chips=1,
        architecture=cell.architecture, shapes=cell.architecture.shapes(cell.config), seq=8192, rows_per_replica=1,
        tokens_per_step_per_replica=8192, device_kind="TPU v5 lite",
    )


def test_kernel_readers_on_a_synthetic_trace(cell):
    call = "%{} = bf16[1,32,8192,128] custom-call(bf16[1,32,8192,128] %p), custom_call_target=tpu_custom_call"
    ops = []
    for step in range(2):
        at = 1.0 + 0.5 * step
        ops += [
            ("%fusion.9 = bf16[8192,2560] fusion(%p)", at, 0.2),
            (call.format("kda_fwd.3"), at + 0.20, 0.040), (call.format("kda_fwd.4"), at + 0.24, 0.040),
            (call.format("kda_bwd.5"), at + 0.28, 0.100),
            (call.format("flash_fwd.1"), at + 0.38, 0.006), (call.format("flash_dq.1"), at + 0.386, 0.008),
            (call.format("flash_dkv.1"), at + 0.394, 0.010),
            (call.format("jvp_jit_gmm__.2"), at + 0.41, 0.003),
            (call.format("transpose_jvp_jit_tgmm___.7"), at + 0.42, 0.002),
            # operations that only MENTION a kernel, as their operand
            ("%get-tuple-element.3 = f32[8] get-tuple-element(%kda_bwd.5), index=4", at + 0.43, 0.001),
            ("%copy.8 = bf16[8192,768] copy(%jvp_jit_gmm__.2)", at + 0.44, 0.001),
        ]
    flight = [
        dict(name="MOE_ROUTE", t=1.4, rows_here=[2048.0] * 6, load_max=[160.0] * 6, load_mean=[128.0] * 6),
        dict(name="MOE_ROUTE", t=1.9, rows_here=[2048.0] * 6, load_max=[192.0] * 5 + [256.0], load_mean=[128.0] * 6),
        dict(name="MOE_ROUTE", t=0.5, rows_here=[9.0] * 6, load_max=[9.0] * 6, load_mean=[1.0] * 6),  # before the window
    ]
    sources = _trace_sources(cell, ops, flight)
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(sources)  # noqa: E731
    assert read("kda_fwd_ms") == pytest.approx(80.0) and read("kda_bwd_ms") == pytest.approx(100.0)
    assert read("mla_flash_ms") == pytest.approx(24.0) and read("moe_gmm_ms") == pytest.approx(5.0)
    count = cell.architecture.ling_flops
    s = sources["shapes"]
    from ftbench import flops

    for name, need, seconds in (
        ("kda_roofline", count.kda_step(s, 1, 8192), 0.180),
        ("flash_roofline", count.mla_flash_step(s, 1, 8192), 0.024),
        ("moe_gmm_roofline", count.gmm_step(s, 2048.0), 0.005),
    ):
        assert read(name) == pytest.approx(flops.roofline_pct(*need, seconds, "TPU v5 lite")["pct"])
        assert 0 < read(name) < 100
    assert read("moe_rows_here_per_step") == pytest.approx(6 * 2048.0)
    assert read("moe_load_max_over_mean") == pytest.approx((1.25 + 2.0) / 2)
    busy = (0.2 + 0.18 + 0.024 + 0.005 + 0.002)  # a step's operations, none overlapping
    assert read("step_mfu_pct") == pytest.approx(
        100 * 8192 / busy * count.train_flops_per_token(s, 8192) / 197e12
    )


NEW_READERS = (
    "kda_fwd_ms", "kda_bwd_ms", "kda_roofline", "mla_flash_ms", "moe_gmm_ms",
    "moe_gmm_roofline", "moe_rows_here_per_step", "moe_load_max_over_mean",
)  # ``mla_flash_roofline`` and ``ling_step_mfu_pct`` were two more until PR 66: the cell is on the folded readers' lists


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_it(cell, name):
    """The parent commit has no such kernel, event or architecture: the
    reader returns None, never raises, and the metric is left out."""
    llama = spec.load_cell("mistral7b-ws1-steady")
    ops = [("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.0, 0.1), ("%fusion.1 = bf16[2048,4096] fusion(%p)", 1.5, 0.1)]
    sources = _trace_sources(cell, ops)
    sources["shapes"] = llama.architecture.shapes(llama.config)
    read = spec.load_metric(name, BENCH_DIR).read
    assert read(sources) is None
    assert read(dict(sources, trace=None)) is None


# ``moe_gmm_roofline`` with them since PR 66: it counts with the cell's own architecture
ANY_EXPERT_CELL = ("moe_gmm_ms", "moe_gmm_roofline", "moe_rows_here_per_step", "moe_load_max_over_mean")


def test_new_readers_list_this_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert CELL in listed[name]["workloads"] and listed[name]["moves"] == "tokens_per_s_per_chip"
        # the readers of the experts read the trace and MOE_ROUTE alone, so
        # any expert cell may join them; Ling's kernels are Ling's
        assert name in ANY_EXPERT_CELL or listed[name]["workloads"] == [CELL]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    # no count of cells or configurations: a later PR adds its own
    assert entry["config"] in [c["name"] for c in bench["configs"]]


@pytest.mark.parametrize(
    "trace,expects",
    [
        (0, {"tokens_per_s_per_chip", "setup_s"}),
        (1, {"quorum_ms", "commit_vote_ms", "moe_rows_here_per_step", "moe_load_max_over_mean"}),
    ],
)
def test_rehearsal_walks_the_cell(trace, expects):
    """The whole path on the CPU at the toy widths: Manager, ``HSDPTrainer``,
    the bias update, the flight events, the float32 reference, the readers."""
    done = _run(["--workload", CELL, "--seed", "3000000029", "--seconds", "2",
                 "--trace", str(trace), "--rehearse"], devices=2)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = _lines(done.stdout)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert (set(last["would_report"]) >= expects) if trace else (set(last["would_report"]) == expects)
    assert not {"kda_fwd_ms", "kda_roofline", "step_device_ms"} & set(last["would_report"])
    checks = next(l for l in lines if "checks" in l)
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["attention"][0].startswith("plain: ") and checks["params_M"] == pytest.approx(0.4288, abs=1e-3)
