"""``ftbench/device_scopes.py``: the device's operations with the scope path
of their event metadata, their OWN time on the nested "XLA Ops" line, and the
eleven metrics that share the compiled step out by the program's named parts
(``torchft_tpu/obs/spans.py``, ``DEVICE_PARTS``); on synthetic planes whose
numbers are known and on the small trace recorded on the chip."""

import os

import pytest

from ftbench import device_scopes, sources as bench_sources, spec, trace_reduce
from tests._ftbench_view import BENCH_DIR, bench, reader_entry

SMALL = os.path.join(BENCH_DIR, "tests", "data", "small.xplane.pb")

LAYER = "jit(_step)/jvp(tpuft.layers)/while/body/closed_call"
BACK = "jit(_step)/transpose(jvp(tpuft.layers))/while/body/closed_call/checkpoint"
# one step, ms from its start: (id, name, start, end, path, category); the
# while lies over everything to 60, the conditional over 25-45
STEP = [
    (1, "%while.1 = (s32[], f32[8]) while(%tuple)", 0, 60, "jit(_step)/jvp(tpuft.layers)/while:", "while"),
    (2, "%fusion.1 = bf16[8,8] fusion(%p)", 0, 10, LAYER + "/tpuft.mixer_glue/tpuft.mixer_proj/dot_general:", "convolution fusion"),
    (3, "%flash_fwd.2 = bf16[8,8] custom-call(%q)", 10, 20, LAYER + "/tpuft.mixer_glue/flash_fwd/pallas_call:", "custom-call"),
    (4, "%conditional.3 = f32[8] conditional(%s)", 20, 50, LAYER + "/tpuft.experts_dispatch/cond:", "conditional"),
    (5, "%gmm.4 = bf16[8,8] custom-call(%x)", 25, 35, LAYER + "/tpuft.experts_dispatch/jit(gmm)/pallas_call:", "custom-call"),
    (6, "%fusion.5 = f32[8] fusion(%y)", 35, 40, LAYER + "/tpuft.experts_dispatch/scatter-add:", "data formatting"),
    (7, "%fusion.6 = f32[8] fusion(%y)", 40, 45, LAYER + "/tpuft.experts_dispatch/mul:", "loop fusion"),
    (8, "%bitcast_dynamic-update-slice_fusion.7 = f32[4,8] fusion(%g)", 50, 58,
     "jit(_step)/transpose(jvp(tpuft.layers))/while/body/dynamic_update_slice:", "convolution fusion"),
    (9, "%fusion.8 = f32[8] fusion(%z)", 60, 66, BACK + "/rematted_computation/tpuft.stream/mul:", "loop fusion"),
    (10, "%kda_fwd.9 = f32[8] custom-call(%k)", 66, 70, BACK + "/rematted_computation/tpuft.mixer_glue/kda_fwd/pallas_call:", "custom-call"),
    (11, "%copy.10 = f32[8] copy(%c)", 70, 72, None, "data formatting"),
    (12, "%fusion.11 = f32[8,64] fusion(%h)", 72, 80, "jit(_step)/jvp(tpuft.head)/dot_general:", "convolution fusion"),
    (13, "%gather.12 = f32[8] gather(%e)", 80, 82, "jit(_step)/jvp(tpuft.embed)/gather:", "gather"),
    (14, "%fusion.13 = f32[8] fusion(%o)", 90, 96, "jit(_update)/tpuft.optimizer/mul:", "loop fusion"),
]
# a step's own milliseconds by metric, by hand: the conditional keeps 30 - 10 - 5 - 5
# and the while 60 - 10 - 10 - 30 - 8; the three kernels (10 + 10 + 4) are in no part
EXPECTS = {
    "xla_mixer_proj_ms": 10.0, "xla_mixer_glue_ms": 0.0, "xla_ffn_ms": 0.0, "xla_stream_ms": 6.0,
    "xla_head_ms": 10.0, "moe_route_ms": 0.0, "moe_dispatch_ms": 20.0, "xla_layer_scan_ms": 10.0,
    "optimizer_ms": 6.0, "step_remat_ms": 10.0, "xla_unscoped_ms": 2.0,
}
PARTS = sorted(set(EXPECTS) - {"step_remat_ms", "xla_unscoped_ms"})
KERNELS_MS, BUSY_MS, STEPS = 24.0, 88.0, 2


def _planes(scoped=True, update_scoped=True):
    """Two such steps, 100 ms apart, on a device plane behind a host plane;
    the categories of the two custom calls are strings kept by reference.
    ``scoped`` False: the same operations as a program without scopes names
    them (a parent commit); ``update_scoped`` False: the update program alone
    without its scope (the executable a parent left in the compile cache)."""
    events, metadata = [], []
    for ident, name, start, end, path, category in STEP:
        for step in range(STEPS):
            ps = int((step * 100 + start) * 1e9)
            events.append(f"events {{ metadata_id: {ident} offset_ps: {ps} duration_ps: {int((end - start) * 1e9)} "
                          f"stats {{ metadata_id: 9 uint64_value: 1 }} }}")
        stats = []
        if path is not None:
            if not scoped or (not update_scoped and path.startswith("jit(_update)")):
                path = "/".join(c for c in path.replace("(tpuft.layers)", "()").replace("(tpuft.head)", "()")
                                .replace("(tpuft.embed)", "()").split("/") if not c.startswith("tpuft."))
            stats.append(f'stats {{ metadata_id: 1 str_value: "{path}" }}')
        stats.append('stats { metadata_id: 2 ref_value: 5 }' if category == "custom-call"
                     else f'stats {{ metadata_id: 2 str_value: "{category}" }}')
        stats.append('stats { metadata_id: 3 str_value: "/root/repo/torchft_tpu/models/llama.py:400" }')
        stats.append("stats { metadata_id: 4 int64_value: 1024 }")
        stats.append("stats { metadata_id: 6 double_value: 0.5 }")
        metadata.append(f'event_metadata {{ key: {ident} value {{ id: {ident} name: "{name}" {" ".join(stats)} }} }}')
    return f"""
planes {{ id: 9 name: "/host:CPU"
  lines {{ id: 7 name: "python" timestamp_ns: 1000000000
    events {{ metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "tpuft/step/grad" }} }} }}
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 82000000000 }} }}
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000000
    {" ".join(events)} }}
  {" ".join(metadata)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "source" }} }}
  stat_metadata {{ key: 4 value {{ id: 4 name: "flops" }} }}
  stat_metadata {{ key: 5 value {{ id: 5 name: "custom-call" }} }}
  stat_metadata {{ key: 6 value {{ id: 6 name: "Time Scale Multiplier" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "run_id" }} }} }}
"""


def _serialized(scoped=True, update_scoped=True):
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(_planes(scoped, update_scoped))


def _sources(data, t_first=1.0, t_last=1.2):
    """What a reader is handed of a traced run over these planes: the two
    steps whole inside the trace, and ``trace_reduce``'s view of the same
    bytes for the readers that were there."""
    from jax.profiler import ProfileData

    space = trace_reduce.from_profile(ProfileData.from_serialized_xspace(data))
    mid = (t_first + t_last) / 2
    return dict(
        trace=dict(
            per_device=trace_reduce.summarize(space), offset=0.0,
            traced_steps=[[dict(t_enter=t_first, t_exit=mid), dict(t_enter=mid, t_exit=t_last)]],
        ),
        replicas=1, groups_share_chip=False,
    )


@pytest.fixture(scope="module")
def run():
    data = _serialized()
    return dict(planes=device_scopes.parse(data), sources=_sources(data))


def test_the_ops_line_comes_back_with_its_metadata(run):
    assert sorted(run["planes"]) == [0]  # the host plane is passed over
    ops = run["planes"][0]
    assert len(ops) == len(STEP) * STEPS
    by_name = {op["name"].split(" ")[0]: op for op in ops}
    proj = by_name["%fusion.1"]
    assert (proj["part"], proj["pass"], proj["remat"], proj["kernel"]) == ("mixer_proj", "fwd", False, False)
    assert proj["category"] == "convolution fusion" and proj["source"].endswith("llama.py:400")
    flash = by_name["%flash_fwd.2"]  # its category is a string kept by reference
    assert (flash["part"], flash["kernel"], flash["category"]) == ("mixer_glue", True, "custom-call")
    again = by_name["%kda_fwd.9"]
    assert (again["pass"], again["remat"], again["kernel"]) == ("bwd", True, True)
    write = by_name["%bitcast_dynamic-update-slice_fusion.7"]
    assert (write["part"], write["pass"], write["remat"]) == ("layers", "bwd", False)
    bare = by_name["%copy.10"]  # an operation with no path at all
    assert (bare["tf_op"], bare["part"], bare["pass"], bare["kernel"]) == ("", None, "other", False)
    assert by_name["%fusion.13"]["pass"] == "other" and by_name["%fusion.13"]["part"] == "optimizer"
    # the clock is trace_reduce's
    mine = sorted((op["start"], op["dur_s"]) for op in ops)
    theirs = sorted((s, d) for _, s, d in run["sources"]["trace"]["per_device"][0]["ops"])
    assert mine == [pytest.approx(t, abs=2e-9) for t in theirs]


def test_own_times_add_up_to_the_busy_union(run):
    ops, steps = device_scopes.in_stretch(run["sources"], run["planes"])
    assert steps == STEPS
    own = {op["name"].split(" ")[0]: op["own_s"] for op in ops}
    assert own["%while.1"] == pytest.approx(0.002) and own["%conditional.3"] == pytest.approx(0.010)
    assert own["%gmm.4"] == pytest.approx(0.010) and own["%fusion.1"] == pytest.approx(0.010)
    busy = bench_sources.device_busy_s(run["sources"])[0]
    assert sum(op["own_s"] for op in ops) == pytest.approx(busy) == pytest.approx(STEPS * BUSY_MS / 1e3)
    assert all(op["own_s"] > 0 for op in ops)
    # a stretch that begins 5 ms into the first while and ends 4 ms into the last update
    cut = _sources(_serialized(), 1.005, 1.194)
    ops, _ = device_scopes.in_stretch(cut, run["planes"])
    assert sum(op["own_s"] for op in ops) == pytest.approx(bench_sources.device_busy_s(cut)[0]) == pytest.approx(0.169)
    first = min(ops, key=lambda op: (op["start"], -op["dur_s"]))
    assert first["name"].startswith("%while.1") and first["own_s"] == pytest.approx(0.002)


@pytest.mark.parametrize("name", sorted(EXPECTS))
def test_scope_reader_on_synthetic_planes(run, name, monkeypatch):
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: run["planes"])
    metric = spec.load_metric(name, BENCH_DIR)
    assert metric.read(run["sources"]) == pytest.approx(EXPECTS[name], abs=1e-9)
    # no trace (an untraced run), and a trace with no device plane (the CPU rehearsal)
    assert metric.read(dict(run["sources"], trace=None)) is None
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    assert metric.read(run["sources"]) is None


def test_the_parts_tile_the_step(run, monkeypatch):
    """ISSUE 37's identity: the nine parts, the unscoped rest and the
    kernels' own time are ``step_device_ms``."""
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: run["planes"])
    read = lambda name: spec.load_metric(name, BENCH_DIR).read(run["sources"])  # noqa: E731
    kernels = device_scopes.own_ms_per_step(run["sources"], lambda op: op["kernel"])
    assert kernels == pytest.approx(KERNELS_MS)
    total = sum(read(name) for name in PARTS) + read("xla_unscoped_ms") + kernels
    assert len(PARTS) == 9 and total == pytest.approx(read("step_device_ms")) == pytest.approx(BUSY_MS)


@pytest.mark.parametrize("name", sorted(EXPECTS))
def test_scope_reader_reads_nothing_from_a_program_without_scopes(name, monkeypatch):
    """The parent's trace holds the same operations and no ``tpuft.``
    anywhere: nothing, and no error (the driver runs these readers over the
    parent's checkout too)."""
    data = _serialized(scoped=False)
    planes = device_scopes.parse(data)
    assert len(planes[0]) == len(STEP) * STEPS and not any(op["scoped"] for op in planes[0])
    assert any(op["remat"] for op in planes[0]) and any(op["kernel"] for op in planes[0])
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    assert spec.load_metric(name, BENCH_DIR).read(_sources(data)) is None


@pytest.mark.parametrize("name", sorted(EXPECTS))
def test_scope_reader_where_the_update_program_came_from_a_parents_cache(name, monkeypatch):
    """The compile cache does not key on scopes: the change's update program,
    whose lowered text is the parent's, is handed out as the parent compiled
    it, with ``jit(_update)/mul`` for a path (seen on the chip, PR 37).  It is
    one part whole, so the readers go by the program and read what they read
    from a fresh compile."""
    data = _serialized(update_scoped=False)
    planes = device_scopes.parse(data)
    stale = [op for op in planes[0] if op["tf_op"].startswith("jit(_update)")]
    assert len(stale) == STEPS and all(op["part"] == "optimizer" and not op["scoped"] for op in stale)
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: planes)
    assert spec.load_metric(name, BENCH_DIR).read(_sources(data)) == pytest.approx(EXPECTS[name], abs=1e-9)


def test_the_recorded_trace_as_it_is():
    with open(SMALL, "rb") as f:
        planes = device_scopes.parse(f)
    assert sorted(planes) == [0] and len(planes[0]) == 9
    fusions = [op for op in planes[0] if op["name"].startswith("%fusion")]
    assert len(fusions) == 3
    for op in fusions:
        assert op["tf_op"] == "jit(small_step)/dot_general:" and op["category"] == "convolution fusion"
        assert op["source"].endswith("record_small_trace.py:22")
        assert (op["part"], op["pass"], op["remat"], op["kernel"]) == (None, "other", False, False)
    # the same events on the same clock as the reader that was there
    from jax.profiler import ProfileData

    theirs = trace_reduce.device_planes(trace_reduce.from_profile(ProfileData.from_file(SMALL)))[0][trace_reduce.OPS_LINE]
    assert sorted(op["start"] for op in planes[0]) == [pytest.approx(s, abs=2e-9) for _, s, _ in sorted(theirs, key=lambda e: e[1])]
    # bytes serve as well as a file, and a file that is no trace is an error, not an empty answer
    with open(SMALL, "rb") as f:
        assert device_scopes.parse(f.read()) == planes
    with pytest.raises(ValueError, match="not an XSpace"):
        device_scopes.parse(b"\x0f\x01")


def test_load_finds_the_trace_as_the_harness_does(tmp_path):
    assert device_scopes.load(str(tmp_path)) == {}
    folder = tmp_path / "out" / "trace" / "plugins" / "profile" / "2026_09_29"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_serialized())
    planes = device_scopes.load(str(tmp_path))
    assert len(planes[0]) == len(STEP) * STEPS and device_scopes.load(str(tmp_path)) is planes


def test_the_table_a_builder_wants_first(run, capsys, monkeypatch):
    t = device_scopes.table(*device_scopes.in_stretch(run["sources"], run["planes"]))
    assert t["busy_ms_per_step"] == pytest.approx(BUSY_MS)
    parts = {e["part"]: e["ms_per_step"] for e in t["parts"]}
    assert parts == pytest.approx(dict(kernels=24.0, experts_dispatch=20.0, mixer_proj=10.0, layers=10.0,
                                       stream=6.0, head=8.0, embed=2.0, optimizer=6.0, unscoped=2.0))
    rows = {(r["part"], r["pass"], r["remat"]): r["ms_per_step"] for r in t["rows"]}
    assert rows[("kernels", "bwd", True)] == pytest.approx(4.0) and rows[("stream", "bwd", True)] == pytest.approx(6.0)
    assert rows[("layers", "fwd", False)] == pytest.approx(2.0) and rows[("layers", "bwd", False)] == pytest.approx(8.0)
    assert sum(r["share_pct"] for r in t["rows"]) == pytest.approx(100.0)
    assert t["by_kind"]["experts_dispatch"] == dict(
        category={"conditional": pytest.approx(10.0), "data formatting": pytest.approx(5.0), "loop fusion": pytest.approx(5.0)},
        primitive={"cond": pytest.approx(10.0), "scatter-add": pytest.approx(5.0), "mul": pytest.approx(5.0)},
    )
    assert t["by_kind"]["unscoped"]["primitive"] == {"(no path)": pytest.approx(2.0)}
    top = t["longest"]["layers"][0]
    assert top["name"].startswith("%bitcast_dynamic-update-slice_fusion.7") and top["category"] == "convolution fusion"
    assert top["tf_op"].endswith("dynamic_update_slice:") and top["calls_per_step"] == 1.0
    # and as ``python -m ftbench.device_scopes <series file> 1`` prints it
    from ftbench import program_spans

    monkeypatch.setattr(program_spans, "sources_of_run", lambda path: run["sources"])
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: run["planes"])
    assert device_scopes.main(["device_scopes", "a series file", "1"]) == 0
    printed = capsys.readouterr().out.splitlines()
    assert printed[0] == "2 steps, busy 88.000 ms a step (own times summed)"
    assert [line.split() for line in printed if line.startswith("kernels   ")] == [
        ["kernels", "fwd", "20.000", "22.73"], ["kernels", "bwd", "remat", "4.000", "4.55"],
    ]
    assert "  by category: conditional 10.00, data formatting 5.00, loop fusion 5.00" in printed
    assert sum(line.startswith("     ") for line in printed) == len(t["parts"])  # one longest operation a part
    monkeypatch.setattr(device_scopes, "load", lambda bench_dir=None: {})
    assert device_scopes.main(["device_scopes", "a series file"]) == 1


# the one-replica cells the readers were written for (PR 37, and PR 41's, which
# has every part): the dense model has no experts, Keye's layers no dense FFN
WRITTEN_FOR = (
    "mistral7b-ws1-steady", "ling3flash-ws1-seq8k", "keye2-ws1-seq16k", "nemotron3nano-ws1-seq16k",
    "trinitymini-ws1-seq16k",
)
WITHOUT_THE_PART = {
    "xla_ffn_ms": ("keye2-ws1-seq16k",), "moe_route_ms": ("mistral7b-ws1-steady",),
    "moe_dispatch_ms": ("mistral7b-ws1-steady",),
}


@pytest.mark.parametrize("name", sorted(EXPECTS))
def test_the_entry_benchmark_json_lists(name):
    without = WITHOUT_THE_PART.get(name, ())
    entry = reader_entry(
        name, cells=[c for c in WRITTEN_FOR if c not in without], better="lower", unit="ms",
        layer="experts" if name.startswith("moe_") else "compiled step",
    )
    assert not set(without) & set(entry["workloads"])
    # each of them reports the end-to-end metric the entry moves
    (moved,) = [m for m in bench()["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
