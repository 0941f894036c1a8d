"""Every test's own time limit (``tests/conftest.py`` ``time_limit``): a body
past its limit fails with every thread's stack in what it reports, the run
goes on, and no timer outlives a body."""

import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

from tests import conftest as a_copy  # pytest's own is the module "conftest": the fixture below


@pytest.fixture()
def conftest(request):
    """``tests/conftest.py`` as pytest loaded it, whose constants the hooks read."""
    return request.config.pluginmanager.get_plugin(a_copy.__file__)


@pytest.fixture()
def no_limit_around(monkeypatch, conftest):
    """These tests run under the hook's own limit, which would be the timer
    they find: this one's body runs without (``setitimer(0)`` sets none), as a
    body outside a test does."""
    monkeypatch.setattr(conftest, "TEST_LIMIT_S", 0.0)


def _alarm():
    """(SIGALRM's handler, whether a timer runs)."""
    return signal.getsignal(signal.SIGALRM), signal.getitimer(signal.ITIMER_REAL) != (0.0, 0.0)


def parked_in_a_named_function(until):
    until.wait()


# ``faulthandler`` writes a hundred threads' stacks and then "...": a worker late
# in a run holds more (the third whole run of PR 55 met it), the main thread last
@pytest.mark.parametrize("others", [1, 120], ids=["a_second_thread", "more_threads_than_faulthandler_writes"])
def test_a_body_past_its_limit_fails_with_every_threads_stack(others, capfd, no_limit_around, conftest):
    found = _alarm()
    assert not found[1]
    until = threading.Event()
    parked = [threading.Thread(target=parked_in_a_named_function, args=(until,), daemon=True) for _ in range(others)]
    for other in parked:
        other.start()
    began = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception) as caught:
            with conftest.time_limit(1.0, "the sleeper"):
                time.sleep(3.0)
        assert time.monotonic() - began < 2.5  # the sleep was cut, not waited out
    finally:
        until.set()
        for other in parked:
            other.join()
    said = str(caught.value)
    assert "the sleeper ran past its limit of 1 s" in said
    assert "parked_in_a_named_function" in said  # the second thread, by where it stands
    assert "test_a_body_past_its_limit_fails_with_every_threads_stack" in said  # and this one, whatever the number of threads
    assert "parked_in_a_named_function" in capfd.readouterr().err  # the log has them too
    assert _alarm() == found


def test_a_body_that_ends_in_time_leaves_no_timer_set(no_limit_around, conftest):
    found = _alarm()
    assert not found[1]
    with conftest.time_limit(5.0, "a quick body"):
        assert _alarm()[1] and _alarm()[0] is not found[0]
    assert _alarm() == found
    time.sleep(0.01)  # and nothing fires after it


def test_a_body_that_raises_leaves_no_timer_set(no_limit_around, conftest):
    found = _alarm()
    with pytest.raises(KeyError):
        with conftest.time_limit(5.0, "a body that raises"):
            raise KeyError("its own")
    assert _alarm() == found and not found[1]


def test_a_thread_that_is_not_the_main_one_runs_its_body_without_a_timer(no_limit_around, conftest):
    found, seen = _alarm(), []

    def body():
        with conftest.time_limit(0.05, "a thread's body"):
            seen.append(_alarm())
            time.sleep(0.1)
        seen.append("ended")

    thread = threading.Thread(target=body)
    thread.start()
    thread.join()
    assert seen == [found, "ended"] and not found[1]


@pytest.mark.parametrize(
    "path,limit",
    [
        ("tests/test_ftbench_compile_mistral_7b_v0_3_1x1.py", "COMPILE_CASE_LIMIT_S"),
        ("tests/test_ftbench_compile.py", "TEST_LIMIT_S"),  # the file's own tests compile nothing
        ("tests/test_manager_integ.py", "TEST_LIMIT_S"),
    ],
)
def test_a_compile_case_gets_the_long_limit_and_any_other_test_the_short_one(path, limit, conftest):
    assert conftest.limit_for(SimpleNamespace(fspath=path)) == getattr(conftest, limit)
    assert conftest.TEST_LIMIT_S < conftest.COMPILE_CASE_LIMIT_S


def test_this_test_runs_under_the_limit_the_hook_gives_it(request, conftest):
    """The hook wrapper is on: the timer runs while a test's body does."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.limit_for(request.node) == conftest.TEST_LIMIT_S


def test_a_test_past_its_limit_fails_and_the_run_goes_on(tmp_path):
    """A run of two tests under the hooks themselves, the short limit set to
    half a second: the first hangs and fails with the stacks, the second runs."""
    (tmp_path / "conftest.py").write_text(
        "import tests.conftest as ours\n"
        "from tests.conftest import pytest_runtest_call, pytest_runtest_setup  # noqa: F401\n"
        "ours.TEST_LIMIT_S = 0.5\n"
    )
    (tmp_path / "test_two.py").write_text(
        "import threading\n"
        "def parked_here(until):\n"
        "    until.wait()\n"
        "def test_that_hangs():\n"
        "    until = threading.Event()\n"
        "    threading.Thread(target=parked_here, args=(until,), daemon=True).start()\n"
        "    try:\n"
        "        until.wait(30)\n"
        "    finally:\n"
        "        until.set()\n"
        "def test_that_comes_after():\n"
        "    pass\n"
    )
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:xdist", "--rootdir", str(tmp_path), str(tmp_path)],
        cwd=tmp_path, env=dict(os.environ, PYTHONPATH=a_copy.ROOT), capture_output=True, text=True, timeout=120,
    )
    out = run.stdout + run.stderr
    assert run.returncode == 1 and "1 failed, 1 passed" in out, out
    assert "test_two.py::test_that_hangs ran past its limit of 0.5 s" in out and "parked_here" in out, out
