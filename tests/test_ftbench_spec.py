"""Tier-1's view of ``ftbench/tests/test_ftbench_spec.py``: the benchmark's
tests, imported (``tests/_ftbench_view.py`` says why, and the rule a view
keeps), and the two things tier-1 adds to them: no width of any source is cut,
as a rule by key, and the rule of the views is held on the file a later PR
would leave."""

import importlib
import json
import os

import pytest

from ftbench.tests.test_ftbench_spec import *  # noqa: F401,F403
from ftbench.tests.test_ftbench_spec import ROOT, _with_a_further_cell
from tests._ftbench_view import bench

# the published configurations of the catalog's architectures, where the
# machine has the guides; each cell's own test file holds its widths by value
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_a_configuration_is_its_source_but_for_the_keys_reduced_names():
    """The benchmark's ``test_contract_limits`` holds that ``reduced`` names
    no width.  What its file says of itself holds the rest, for any family and
    with no table of widths here: ``published`` keeps the source's value of
    every key that was cut and of no other, so every other key of the file IS
    the source's; and where the catalog has the source's configuration, the
    file differs from it in the keys ``reduced`` names and in no other.  A key
    of ``reduced`` that the source does not carry (a pattern the released code
    derives from other keys, spelled out because it was cut) has its published
    value in the file's ``published`` all the same."""
    rows = {}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = {row["source_url"]: row["config"] for row in map(json.loads, f)}
    for entry in bench()["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert sorted(config["published"]) == sorted(config["reduced"]) == sorted(entry["reduced"]), entry["name"]
        published = rows.get(entry["source"])
        if published is not None:
            assert {k for k, v in published.items() if config.get(k) != v} == set(entry["reduced"]) & set(published), entry["name"]
            assert all(config["published"][k] == published[k] for k in entry["reduced"] if k in published), entry["name"]


# ----------------------------------------------------------------------
# the rule of ``tests/_ftbench_view.py``, held as the benchmark holds its own
# (``test_a_further_cell_fails_none_of_the_list_tests``): every test under
# ``tests/`` that reads BENCHMARK.json's lists, on the file as it is and on
# the file a later PR would leave
# ----------------------------------------------------------------------

LIST_TESTS = [
    # the benchmark's list tests as tier-1 sees them: a view overrides none
    ("test_ftbench_ling", "test_new_readers_list_this_cell_alone"),
    ("test_ftbench_indexed", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_ssm", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_swa", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_prerouted", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_ssmdense", "test_the_cell_and_the_lists_it_joined"),
    ("test_ftbench_program_spans", "test_new_readers_are_the_eighteen_benchmark_json_lists"),
    ("test_ftbench_program_spans", "test_the_four_chip_cell_and_the_lists_it_joined"),
    # the tests of the readers that were written under ``tests/``
    ("test_ftbench_program_spans", "test_heal_serve_ahead_pct_is_its_entry_and_lists_the_kill_cell"),
    ("test_ftbench_program_spans", "test_normalize_in_ring_pct_is_its_entry_and_lists_the_steady_two_replica_cell"),
    ("test_ftbench_program_spans", "test_d2h_direct_pct_is_its_entry_and_lists_the_four_chip_cell"),
    ("test_ftbench_program_spans", "test_the_piece_readers_are_their_entries_and_list_the_two_steady_cells"),
    ("test_ftbench_program_spans", "test_the_lane_reader_is_its_entry_and_lists_the_two_steady_cells"),
    ("test_ftbench_buffer_fill", "test_the_reader_is_its_entry_and_lists_the_four_expert_cells"),
    ("test_ftbench_device_scopes", "test_the_entry_benchmark_json_lists"),
    ("test_ftbench_spec", "test_a_configuration_is_its_source_but_for_the_keys_reduced_names"),
]


def _cases(test):
    """The arguments of every case of ``test``: its parametrisation's."""
    marks = [m for m in getattr(test, "pytestmark", []) if m.name == "parametrize"]
    if not marks:
        return [{}]
    (mark,) = marks
    names = [n.strip() for n in mark.args[0].split(",")]
    return [dict(zip(names, values if len(names) > 1 else (values,))) for values in mark.args[1]]


def _later_files(read):
    """``read`` as later PRs would leave it (the benchmark's
    ``_with_a_further_cell``: a reader before the first and one after the
    last, a cell and a configuration at the end, the cell in every list that
    its like shares with another), once for every cell of today as the one the
    further cell is like: a later cell may join any cell's lists.  (The kill
    cell shares no list, and the helper takes a cell that shares over ten.)"""
    lists = [m["workloads"] for m in read["end_to_end"] + read["per_layer"] if len(m.get("workloads", ())) > 1]
    for like in read["workloads"]:
        if sum(like["name"] in cells for cells in lists) > 10:
            others = [w for w in read["workloads"] if w["name"] != like["name"]]
            yield _with_a_further_cell(dict(read, workloads=others + [like]))


@pytest.mark.parametrize("module,test", LIST_TESTS, ids=[f"{m[13:]}.{t}" for m, t in LIST_TESTS])
def test_a_further_cell_fails_none_of_tier_1s_list_tests(module, test, monkeypatch):
    listed = getattr(importlib.import_module("tests." + module), test)
    cases = _cases(listed)
    # on the file as it is, then on each file a later PR would leave
    for case in cases:
        listed(**case)
    load = json.load
    for later in _later_files(bench()):

        def shows_the_later_file(f, later=later):
            read = load(f)
            return later if isinstance(read, dict) and "per_layer" in read else read

        with monkeypatch.context() as patched:
            patched.setattr(json, "load", shows_the_later_file)
            names = {group: [e["name"] for e in bench()[group]] for group in ("configs", "workloads", "per_layer")}
            assert "a-further-configuration" in names["configs"] and "a-further-cell" in names["workloads"]
            assert {"a_further_reader.first", "a_further_reader"} <= set(names["per_layer"])
            for case in cases:
                listed(**case)
