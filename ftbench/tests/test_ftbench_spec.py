"""BENCHMARK.json against its files, and adding to the benchmark without
editing it."""

import json
import os
import re
import shutil

import pytest

from ftbench import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


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
        # no width is cut
        assert (config["hidden_size"], config["intermediate_size"]) == (4096, 14336)
        assert (config["num_attention_heads"], config["num_key_value_heads"]) == (32, 8)
        for key in c["reduced"]:
            assert config[key] < config["published"][key]


def test_a_cell_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """What a later PR does: new files and new entries, no edit to a file
    that is there."""
    root = str(tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "ftbench"), os.path.join(root, "ftbench"),
        ignore=shutil.ignore_patterns("out", "__pycache__", "tests"),
    )
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
    traffic = dict(spec.load_cell("mistral7b-ws1-steady").traffic, name="ws1-seq8k", seq_len=8192)
    with open(os.path.join(root, "ftbench", "traffic", "ws1-seq8k.json"), "w") as f:
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
        dict(name="mistral7b-ws1-seq8k", config="mistral-7b-v0.3-1x1-deep",
             traffic="ws1-seq8k", chips=1, why="one sequence of 8,192")
    )
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s_per_chip":
            m["workloads"] = m["workloads"] + ["mistral7b-ws1-seq8k"]
    bench["per_layer"].append(
        dict(name="step_wall_ms", unit="ms", better="lower", source="host_clock",
             layer="entry points", moves="tokens_per_s_per_chip",
             workloads=["mistral7b-ws1-seq8k"])
    )
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("mistral7b-ws1-seq8k", root=root)
    assert cell.config["num_hidden_layers"] == 5 and cell.traffic["seq_len"] == 8192
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
