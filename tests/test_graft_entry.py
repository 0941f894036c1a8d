"""``__graft_entry__.py``, the entry point the driver knows by name: its
jittable forward step compiles, and its multi-device dry run (mesh, pp and
ep passes, two TCP replicas with one restart and a heal) runs to its end on
the eight virtual CPU devices ``conftest.py`` asks for."""

import jax
import pytest

import __graft_entry__ as graft


@pytest.fixture(autouse=True)
def _cpu_on_purpose(monkeypatch, tmp_path):
    # ``_start_backend`` reads the environment: the CPU has to be asked for,
    # and with the cache placed from outside it sets nothing in this process
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_entry_returns_a_step_that_compiles() -> None:
    fn, args = graft.entry()
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text()


def test_dryrun_multichip_runs_to_its_end(capsys) -> None:
    assert len(jax.devices()) >= 8
    graft.dryrun_multichip(8)  # a SystemExit or a failed assert fails the test
    out = capsys.readouterr().out
    assert "dryrun pp:" in out and "dryrun ep:" in out
    assert "restarts=1 healed=True" in out
    assert "dryrun_multichip(8) ok" in out
