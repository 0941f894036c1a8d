"""Records ``data/small.xplane.pb``: three runs of a small jitted program on
the chip with 50 ms of host sleep between them, and the runner's clock mark.
Run once on the chip (``chiprun -- python3 ftbench/tests/record_small_trace.py
chiprun_out/small_trace``); the tests read the recorded file.
"""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from ftbench.trace_reduce import CLOCK_MARK

    @jax.jit
    def small_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation(CLOCK_MARK):
        t_mark = time.monotonic()
    for _ in range(3):
        small_step(x).block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out_dir, "small.xplane.pb"))
    print(f"recorded {found[0]} ({os.path.getsize(found[0])} bytes), mark at {t_mark}")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    sys.exit(main(sys.argv[1]))
