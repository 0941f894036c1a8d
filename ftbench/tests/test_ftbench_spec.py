"""BENCHMARK.json against its files, and adding to the benchmark without
editing it."""

import hashlib
import json
import os
import re
import shutil

import pytest

from ftbench import spec
from ftbench.tests.test_ftbench_rehearsal import _lines, _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
MISTRAL = "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json"
# what ``reduced`` may never name: a hidden, intermediate, latent, state or
# projection size, a head size, an expansion factor, the experts a token
WIDTH = re.compile(r"(_dim|_rank|intermediate_size|state_size|head_size)$|^(hidden_size|expand|num_experts_per_tok)$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


BENCH = _bench()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_files_that_exist(cell):
    loaded = spec.load_cell(cell)
    assert loaded.config["name"] in [c["name"] for c in BENCH["configs"]]
    assert loaded.traffic["replicas"] >= 1
    names = [m["name"] for m in loaded.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer
    for m in loaded.per_layer:
        assert spec.load_metric(m["name"], loaded.bench_dir) is not None, m["name"]
        assert m["moves"] in names, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_file_says_what_benchmark_json_says(metric):
    module = spec.load_metric(metric["name"], os.path.join(ROOT, "ftbench"))
    for key in ("source", "layer", "unit", "moves"):
        assert module.META[key] == metric[key], (metric["name"], key)


# Two readers of the per-call path's spans, which no cell runs since PR 60: their entries went with PR 66 (an
# entry that reads nothing is refused) and their files stay, because tier-1's ``tests/test_ftbench_program_spans.py``
# loads both by name and a ``benchmark`` PR edits nothing under ``tests/``.  With those cases the files go and
# this set is empty again (PERF.md section 7 (cn))
FILES_WITHOUT_AN_ENTRY = frozenset(("sync_normalize_ms", "ring_beside_d2h_pct"))


def test_every_reader_file_is_an_entry():
    """The other way round: a reader's file that no entry names is read by
    no run (a retired entry takes its file with it: PR 58), but the two that
    tier-1 loads by name."""
    files = {f[:-3] for f in os.listdir(os.path.join(ROOT, "ftbench", "layer_metrics"))
             if f.endswith(".py") and not f.startswith("_")}
    entries = {m["name"] for m in BENCH["per_layer"]}
    assert files - entries == FILES_WITHOUT_AN_ENTRY and entries <= files


@pytest.mark.parametrize("name", sorted(FILES_WITHOUT_AN_ENTRY))
def test_a_file_without_an_entry_is_read_by_no_cell(name):
    """No entry, so no cell's ``per_layer`` holds it and no run loads it; the
    file still loads and says where its number would come from."""
    assert all(m["name"] != name for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(m["name"] != name for cell in CELLS for m in spec.load_cell(cell).per_layer)
    module = spec.load_metric(name, os.path.join(ROOT, "ftbench"))
    assert set(module.META) == {"source", "layer", "unit", "moves"} and callable(module.read)
    assert "NO ENTRY" in module.__doc__


def test_contract_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        # no width is cut: ``reduced`` names none (the contract's rule, by the
        # key), so a second family's file needs no edit here; Mistral's own
        # widths are held where the source is Mistral's
        if c["source"] == MISTRAL:
            assert (config["hidden_size"], config["intermediate_size"]) == (4096, 14336)
            assert (config["num_attention_heads"], config["num_key_value_heads"]) == (32, 8)
        for key in c["reduced"]:
            assert not WIDTH.search(key), (c["name"], key)
            assert config[key] < config["published"][key]


def test_a_cell_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """What a later PR does: new files and new entries, no edit to a file
    that is there."""
    root = str(tmp_path)
    _copy_of_the_benchmark(root)
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "ftbench")):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    bench = _bench()
    config = spec.load_cell("mistral7b-ws1-steady").config
    config = dict(config, name="mistral-7b-v0.3-1x1-deep", num_hidden_layers=5)
    with open(os.path.join(root, "ftbench", "configs", "mistral-7b-v0.3-1x1-deep.json"), "w") as f:
        json.dump(config, f)
    traffic = dict(spec.load_cell("mistral7b-ws1-steady").traffic, name="ws1-seq4k", seq_len=4096)
    with open(os.path.join(root, "ftbench", "traffic", "ws1-seq4k.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "ftbench", "layer_metrics", "step_wall_ms.py"), "w") as f:
        f.write(
            "META = dict(source='host_clock', layer='entry points', unit='ms',"
            " moves='tokens_per_s_per_chip')\n\n\n"
            "def read(sources):\n"
            "    from ftbench.sources import all_steps, mean_ms\n\n"
            "    return mean_ms([r['t_exit'] - r['t_enter'] for r in all_steps(sources)])\n"
        )
    bench["configs"].append(
        dict(name="mistral-7b-v0.3-1x1-deep", source=config["source"],
             file="ftbench/configs/mistral-7b-v0.3-1x1-deep.json",
             reduced=["num_hidden_layers"], why="five layers")
    )
    bench["workloads"].append(
        dict(name="mistral7b-ws1-seq4k", config="mistral-7b-v0.3-1x1-deep",
             traffic="ws1-seq4k", chips=1, why="one sequence of 4,096")
    )
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s_per_chip":
            m["workloads"] = m["workloads"] + ["mistral7b-ws1-seq4k"]
    bench["per_layer"].append(
        dict(name="step_wall_ms", unit="ms", better="lower", source="host_clock",
             layer="entry points", moves="tokens_per_s_per_chip",
             workloads=["mistral7b-ws1-seq4k"])
    )
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("mistral7b-ws1-seq4k", root=root)
    assert cell.config["num_hidden_layers"] == 5 and cell.traffic["seq_len"] == 4096
    assert [m["name"] for m in cell.per_layer] == ["step_wall_ms"]
    reader = spec.load_metric("step_wall_ms", cell.bench_dir).read
    steps = [dict(committed=True, t_enter=1.0, t_exit=1.25), dict(committed=True, t_enter=2.0, t_exit=2.35)]
    assert reader(dict(window=[steps])) == pytest.approx(300.0)
    # a reader with nothing to read returns nothing
    assert reader(dict(window=[[]])) is None
    # and the cells that were there still resolve, from files nobody edited
    assert spec.load_cell("mistral7b-ddp2-kill", root=root).traffic["kill"]["victim"] == 1
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path


# -- an architecture is added as files alone ---------------------------------

# What a ``model_config`` PR brings for an architecture the benchmark has not
# seen: the adapter with its own plain reference.  Not Llama's: other config
# keys (``d_model``, ``experts``, ...), another pytree (a LIST of expert
# layers beside the stacked attention leaves, a float32 router among
# ``dtype`` leaves, expert stacks [E, D, F]) and another reference (top-1
# routing, two-matrix relu experts, the gate).  ``GATE`` is the one term the
# wrong reference leaves out.
SWITCH_MOE = '''"""The architecture ``switch_moe``: ``torchft_tpu.models.llama_moe.LlamaMoE``
with every expert local (no mesh), and its plain float32 reference."""

import numpy as np

GATE = %(gate)s

KERNEL_PATHS = {"flash"}
TOY = dict(
    config=dict(d_model=64, d_expert=96, heads=4, kv_heads=2, vocab=384, experts=4, dtype="float32"),
    seq_len=64,
)
# never read on the CPU: float32 has no 8-bit neighbour.  The PR that brings
# the architecture reads it on the chip (tests/calibrate_forward_check.py)
COARSE_RATIO_K = 3.0


def model(config):
    import jax.numpy as jnp

    from torchft_tpu.models.llama_moe import LlamaMoE, LlamaMoEConfig

    return LlamaMoE(LlamaMoEConfig(
        vocab_size=config["vocab"], dim=config["d_model"], n_layers=config["depth"],
        n_heads=config["heads"], n_kv_heads=config["kv_heads"], ffn_hidden=config["d_expert"],
        rope_theta=config["theta"], norm_eps=config["eps"], max_seq_len=config["context"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["dtype"]],
        # room for every token at every expert: nothing is dropped
        num_experts=config["experts"], capacity_factor=float(config["experts"]),
    ))


def shapes(config):
    return {k: config[k] for k in ("d_model", "d_expert", "depth", "heads", "kv_heads", "vocab", "experts")}


def vocab(config):
    return config["vocab"]


def num_params(config):
    d, hd = config["d_model"], config["d_model"] // config["heads"]
    attn = 2 * d * config["heads"] * hd + 2 * d * config["kv_heads"] * hd + 2 * d
    moe = d * config["experts"] + 2 * config["experts"] * d * config["d_expert"]
    return 2 * config["vocab"] * d + d + config["depth"] * (attn + moe)


def token_nll(params, tokens, targets, config):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    heads, kv, eps = config["heads"], config["kv_heads"], config["eps"]
    hd = config["d_model"] // heads
    B, S = tokens.shape

    def rms_norm(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    half = hd // 2
    freqs = 1.0 / (config["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((S, S), bool))
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[np.asarray(tokens)])
        for i, experts in enumerate(params["moe_layers"]):
            w = {k: f32(np.asarray(v)[i]) for k, v in params["layers"].items()}
            h = rms_norm(x, w["attn_norm"])
            q = rope((h @ w["wq"]).reshape(B, S, heads, hd))
            k = jnp.repeat(rope((h @ w["wk"]).reshape(B, S, kv, hd)), heads // kv, axis=2)
            v = jnp.repeat((h @ w["wv"]).reshape(B, S, kv, hd), heads // kv, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
            x = x + attn.reshape(B, S, heads * hd) @ w["wo"]
            h = rms_norm(x, w["mlp_norm"])
            probs = jax.nn.softmax(h @ f32(experts["router"]), axis=-1)
            chosen = jnp.argmax(probs, axis=-1)
            up, down = f32(experts["w_up"])[chosen], f32(experts["w_down"])[chosen]
            out = jnp.einsum("bsf,bsfd->bsd", jax.nn.relu(jnp.einsum("bsd,bsdf->bsf", h, up)), down)
            x = x + (out * jnp.max(probs, axis=-1)[..., None] if GATE else out)
        logits = rms_norm(x, f32(params["final_norm"])) @ f32(params["lm_head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, jnp.asarray(targets)[..., None], axis=-1)[..., 0]
'''

# a reader of the source every cell has since PR 28: a later architecture's
# counters reach its readers as flight events, with no edit to the harness
FLIGHT_READER = (
    "META = dict(source='program_counter', layer='host data plane', unit='events',"
    " moves='tokens_per_s_per_chip')\n\n\n"
    "def read(sources):\n"
    "    events = [e for replica in sources.get('flight') or [] for e in replica]\n"
    "    return float(len(events)) if events else None\n"
)


def _checksum(folder):
    """Every file under ``folder`` that a run does not make, name and bytes."""
    sha, made = hashlib.sha256(), ("out", "__pycache__")
    for here, dirs, files in os.walk(folder):
        dirs[:] = sorted(d for d in dirs if d not in made)
        for name in sorted(files):
            path = os.path.join(here, name)
            sha.update(os.path.relpath(path, folder).encode())
            with open(path, "rb") as f:
                sha.update(f.read())
    return sha.hexdigest()


def _copy_of_the_benchmark(root):
    shutil.copytree(os.path.join(ROOT, "ftbench"), os.path.join(root, "ftbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__", "tests"))
    return os.path.join(root, "ftbench")


def _rehearse(root, cell, trace):
    done = _run(["--workload", cell, "--seed", "2147484721", "--seconds", "2", "--trace", str(trace),
                 "--rehearse"], cwd=root, devices=2)
    return done, _lines(done.stdout)


def test_an_architecture_is_added_as_files_alone(tmp_path, monkeypatch):
    """ISSUE 28's sentence as a test: a second architecture comes as
    ``architectures/<name>.py``, a configuration that names it, a reader and
    entries in ``BENCHMARK.json``; it rehearses end to end with ``correct``
    true, and no file that was there is touched.  With one term of its
    reference left out ``reference_agrees`` fails, and nothing else does."""
    import jax

    # the program is not in that root: it comes from the repo
    monkeypatch.setenv("PYTHONPATH", ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    root = str(tmp_path)
    ours = _copy_of_the_benchmark(root)
    theirs_before, ours_before = _checksum(os.path.join(ROOT, "ftbench")), _checksum(ours)

    bench = _bench()
    config = dict(
        source="a test", depth=2, d_model=1024, d_expert=2048, heads=8, kv_heads=4, vocab=32768, experts=8,
        theta=1e4, eps=1e-5, context=4096, dtype="bfloat16", published={}, reduced={},
        layout=dict(chips_per_group=1, groups_share_chip=False, fsdp=1),
        assumed=dict(learning_rate=3e-4),
    )
    added = [os.path.join(ours, "layer_metrics", "flight_events.py")]
    for name, gate in (("switch_moe", True), ("switch_moe_wrong", False)):
        added += [os.path.join(ours, "architectures", name + ".py"), os.path.join(ours, "configs", name + ".json")]
        with open(added[-2], "w") as f:
            f.write(SWITCH_MOE % dict(gate=gate))
        with open(added[-1], "w") as f:
            json.dump(dict(config, name=name, architecture=name), f)
        bench["configs"].append(dict(name=name, source="a test", file=f"ftbench/configs/{name}.json",
                                     reduced=[], why="top-1 experts, a float32 router, a list in the pytree"))
        bench["workloads"].append(dict(name=name + "-ws1", config=name, traffic="ws1-steady", chips=1,
                                       why="one replica, the architecture's own step"))
    cells = [w["name"] for w in bench["workloads"][-2:]]
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s_per_chip":
            m["workloads"] = m["workloads"] + cells
    with open(added[0], "w") as f:
        f.write(FLIGHT_READER)
    bench["per_layer"].append(dict(name="flight_events", unit="events", better="lower", source="program_counter",
                                   layer="host data plane", moves="tokens_per_s_per_chip", workloads=cells))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("switch_moe-ws1", root=root)
    arch = cell.architecture
    assert arch.__file__.startswith(root) and arch.GATE is True
    assert "hidden_size" not in cell.config and "d_model" in arch.TOY["config"]
    # a pytree that is not Llama's: a list, a float32 router beside ``dtype`` leaves
    shapes = jax.eval_shape(arch.model(cell.config).init, jax.random.PRNGKey(0))
    assert isinstance(shapes["moe_layers"], list) and len(shapes["moe_layers"]) == 2
    first = shapes["moe_layers"][0]
    assert first["router"].dtype == "float32"
    assert first["w_up"].shape == (8, 1024, 2048) and first["w_up"].dtype == "bfloat16"
    assert arch.num_params(cell.config) == sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))

    done, lines = _rehearse(root, "switch_moe-ws1", 1)
    assert done.returncode == 0, done.stderr[-3000:]
    last, checks = lines[-1], next(l for l in lines if "checks" in l)
    assert last["rehearsal"] is True and last["correct"] is True and last["attempted"] > 0
    # sources["flight"] in a cell with no kill: the new reader found events
    assert set(last["would_report"]) >= {"flight_events"}
    assert checks["reference_arm"] == "absolute" and checks["token_rms"] < 1e-4
    assert checks["params_M"] == pytest.approx(arch.num_params(dict(cell.config, **arch.TOY["config"])) / 1e6)

    wrong, lines = _rehearse(root, "switch_moe_wrong-ws1", 0)
    assert wrong.returncode == 1
    checks = next(l for l in lines if "checks" in l)
    assert lines[-1]["correct"] is False and checks["reference_arm"] is None
    assert [k for k, held in checks["checks"].items() if not held] == ["reference_agrees"]
    assert checks["reference_diff"] > 10 * checks["reference_tolerance_abs"]
    # the numbers compared, each beside its limit, are the last lines on standard error
    assert any(l.startswith("ftbench: compared reference_diff_max") for l in wrong.stderr.splitlines()[-5:])

    # the cells that were there still resolve, and nobody edited a file
    assert spec.load_cell("mistral7b-ddp2-kill", root=root).architecture.KERNEL_PATHS == {"flash"}
    assert _checksum(os.path.join(ROOT, "ftbench")) == theirs_before
    for path in added:
        os.remove(path)
    assert _checksum(ours) == ours_before


@pytest.mark.parametrize(
    "edit,error,says",
    [
        (lambda c: c.pop("architecture"), KeyError, ["mistral-7b-v0.3-1x1.json", "architecture"]),
        (lambda c: c.update(architecture="mamba"), FileNotFoundError,
         ["'mamba'", os.path.join("architectures", "mamba.py"), "llama"]),
    ],
    ids=["no_architecture_key", "names_no_file"],
)
def test_a_configuration_has_to_name_an_architecture_that_is_there(tmp_path, edit, error, says):
    root = str(tmp_path)
    _copy_of_the_benchmark(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "ftbench", "configs", "mistral-7b-v0.3-1x1.json")
    with open(path) as f:
        config = json.load(f)
    edit(config)
    with open(path, "w") as f:
        json.dump(config, f)
    with pytest.raises(error) as raised:
        spec.load_cell("mistral7b-ws1-steady", root=root)
    for part in says:
        assert part in str(raised.value), (part, str(raised.value))
    # the other configuration's cells are not in its way
    assert spec.load_cell("mistral7b-ddp2-steady", root=root).architecture.COARSE_RATIO_K == 3.9


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_carries_its_architecture(cell):
    """The contract of ``ftbench/README.md``, "An architecture": what the
    harness asks of the module, and nothing else."""
    arch = spec.load_cell(cell).architecture
    for name in ("model", "shapes", "token_nll", "num_params", "vocab"):
        assert callable(getattr(arch, name)), name
    assert isinstance(arch.KERNEL_PATHS, (set, frozenset)) and arch.KERNEL_PATHS
    assert not any("naive" in path for path in arch.KERNEL_PATHS)
    assert set(arch.TOY) == {"config", "seq_len"} and arch.TOY["seq_len"] >= 16
    assert arch.COARSE_RATIO_K >= 3.0


# ----------------------------------------------------------------------
# the tests that hold a cell to its lists hold what they mean, not a place or
# a count: the next cell edits none of them (PR 43)
# ----------------------------------------------------------------------

LIST_TESTS = [
    ("test_ftbench_ling", "test_new_readers_list_this_cell_alone"),
    ("test_ftbench_indexed", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_ssm", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_swa", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_program_spans", "test_new_readers_are_the_eighteen_benchmark_json_lists"),
    ("test_ftbench_program_spans", "test_the_four_chip_cell_and_the_lists_it_joined"),
]


def _with_a_further_cell(bench):
    """``bench`` as a later PR leaves it: a configuration and a cell after the
    last, the cell's name at the end of every list that two cells or more share
    (a reader of one cell alone is that cell's kernel), a reader of its own
    after the last and, to move every place, one before the first."""
    bench = json.loads(json.dumps(bench))
    last = bench["configs"][-1]
    bench["configs"].append(dict(last, name="a-further-configuration"))
    bench["workloads"].append(dict(bench["workloads"][-1], name="a-further-cell", config="a-further-configuration"))
    shared = [m for m in bench["end_to_end"] + bench["per_layer"]
              if bench["workloads"][-2]["name"] in m.get("workloads", []) and len(m["workloads"]) > 1]
    assert len(shared) > 10
    for m in shared:
        m["workloads"].append("a-further-cell")
    own = dict(bench["per_layer"][-1], workloads=["a-further-cell"])
    bench["per_layer"] = [dict(own, name="a_further_reader.first")] + bench["per_layer"] + [dict(own, name="a_further_reader")]
    return bench


@pytest.mark.parametrize("module,test", LIST_TESTS, ids=[f"{m[13:]}.{t}" for m, t in LIST_TESTS])
def test_a_further_cell_fails_none_of_the_list_tests(module, test, monkeypatch):
    import importlib

    load = json.load

    def further(f):
        read = load(f)
        return _with_a_further_cell(read) if isinstance(read, dict) and "per_layer" in read else read

    theirs = importlib.import_module("ftbench.tests." + module)
    # on the file as it is, then on the file a later PR would leave
    getattr(theirs, test)()
    monkeypatch.setattr(json, "load", further)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f)["workloads"][-1]["name"] == "a-further-cell"
    getattr(theirs, test)()
