"""A value that is costly to make, made once a RUN of the tests.

``--dist load`` hands a file's tests to whichever worker is free, in runs of
two by the end of the collection, and a module-scoped fixture is made anew in
every worker that meets the module: a toy model's ``init`` (one small XLA
program an operation, 10-20 s on a loaded worker), the float32 reference's
side of a comparison, a traced walk of a cell.  ``once_a_run`` makes such a
value in whichever worker asks first and pickles it under a directory that
all workers of the run share; the others read it.
"""

import fcntl
import pickle

# set by ``tests/conftest.py`` at the session's start: pytest's own temporary
# directory of the run (of all its workers, under xdist)
directory = None


def once_a_run(name, make):
    """``make()``, from whichever worker of this run got here first.
    ``name`` is the value's, among everything the run keeps: a file's own
    name in it, and whatever the value depends on.  Outside a run of the
    tests (a file's ``--write``) there is no such directory and ``make()`` it is."""
    if directory is None:
        return make()
    with open(directory / f"{name}.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # until the file is closed
        kept = directory / f"{name}.pickle"
        if not kept.exists():
            kept.write_bytes(pickle.dumps(make()))
        return pickle.loads(kept.read_bytes())
