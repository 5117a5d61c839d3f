"""Records the small trace `test_trace_reduce.py` reads, on the chip:

    python benchmark/tests/record_trace.py    (through the chip tool)

A few launches of a small jitted program under the harness's own
profiler slice and spans, so the fixture has what a real run has: a
TPU plane with "XLA Modules" and "XLA Ops", `bench:*` annotations and
the clock sync. Writes benchmark/tests/data/small.xplane.pb (copy it
back from the chip tool's output directory).
"""

import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import harness

    @jax.jit
    def small_program(x):
        return jax.lax.fori_loop(0, 8, lambda _, a: jnp.tanh(a @ a), x)

    x = jnp.ones((256, 256), jnp.float32)
    small_program(x).block_until_ready()
    cell = type("C", (), {"name": "record", "config": {}, "params": {},
                          "mix": {}})()
    run = harness.Run(cell, 0, 1.0, True, False, time.monotonic())
    tslice = harness.TraceSlice("record", 0.6, 0.6)
    tslice.start()
    time.sleep(0.1)
    for _ in range(6):
        with run.span("launch"):
            small_program(x).block_until_ready()
        with run.span("pause"):
            time.sleep(0.02)
    path = tslice.finish()
    out = os.path.join(REPO, "chiprun_out", "small.xplane.pb")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(path, out)
    print(out, os.path.getsize(out), "bytes; sync_ns", tslice.sync_ns)


if __name__ == "__main__":
    main()
