"""RWLock tests (reference: ``torchft/checkpointing/_rwlock.py`` contract)."""

import threading
import time

import pytest

from torchft_tpu.checkpointing._rwlock import RWLock


def test_many_readers() -> None:
    lock = RWLock(timeout=1.0)
    with lock.r_lock(), lock.r_lock():
        pass


def test_writer_excludes_readers() -> None:
    lock = RWLock(timeout=0.2)
    with lock.w_lock():
        with pytest.raises(TimeoutError):
            lock.r_lock(timeout=0.1)


def test_reader_excludes_writer() -> None:
    lock = RWLock(timeout=0.2)
    with lock.r_lock():
        with pytest.raises(TimeoutError):
            lock.w_lock(timeout=0.1)


def test_writer_preference() -> None:
    """A waiting writer blocks new readers so the train loop can't starve."""
    lock = RWLock(timeout=5.0)
    order = []
    r_guard = lock.r_lock()

    def _writer() -> None:
        with lock.w_lock():
            order.append("w")

    wt = threading.Thread(target=_writer)
    wt.start()
    time.sleep(0.1)  # writer is now queued
    with pytest.raises(TimeoutError):
        lock.r_lock(timeout=0.1)
    r_guard.__exit__(None, None, None)
    wt.join(timeout=5.0)
    assert order == ["w"]
    with lock.r_lock(timeout=0.5):
        pass


def test_concurrent_stress() -> None:
    lock = RWLock(timeout=5.0)
    state = {"v": 0}
    errors = []

    def _reader() -> None:
        try:
            for _ in range(200):
                with lock.r_lock():
                    v = state["v"]
                    assert v % 2 == 0
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def _writer() -> None:
        try:
            for _ in range(100):
                with lock.w_lock():
                    state["v"] += 1
                    state["v"] += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_reader) for _ in range(4)] + [
        threading.Thread(target=_writer) for _ in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert state["v"] == 400


def test_timed_out_wait_rechecks_predicate() -> None:
    """A notify racing the deadline must not produce a spurious
    TimeoutError when the lock became available."""
    lock = RWLock(timeout=5.0)
    lock.r_lock()  # predicate blocked for a writer

    orig_wait = lock._cond.wait

    def wait_times_out_but_lock_freed(timeout=None):
        # simulate: the reader released exactly as our wait timed out
        lock._readers = 0
        return False

    lock._cond.wait = wait_times_out_but_lock_freed  # type: ignore[assignment]
    try:
        guard = lock.w_lock(timeout=0.2)  # must acquire, not raise
    finally:
        lock._cond.wait = orig_wait  # type: ignore[assignment]
    guard.__exit__(None, None, None)


def test_timed_out_wait_rechecks_predicate_reader() -> None:
    lock = RWLock(timeout=5.0)
    lock.w_lock()

    orig_wait = lock._cond.wait

    def wait_times_out_but_lock_freed(timeout=None):
        lock._writer = False
        return False

    lock._cond.wait = wait_times_out_but_lock_freed  # type: ignore[assignment]
    try:
        guard = lock.r_lock(timeout=0.2)
    finally:
        lock._cond.wait = orig_wait  # type: ignore[assignment]
    guard.__exit__(None, None, None)
