#!/usr/bin/env python3
"""Record the small profiler trace that ``benchmark/testdata`` keeps for
the test of the trace reduction (``benchmark/xplane.py``).

    chiprun -- python benchmark/tools/record_trace.py chiprun_out/recorded

A few dispatches of two tiny named programs, host spans around them
(``jax.profiler.TraceAnnotation``, what the program's tracer writes),
and sleeps between them, so that busy time, per-kernel time and the idle
gaps by host span all have something to find. Prints an inventory of the
planes, lines and event names of what it wrote.
"""

import glob
import os
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    @jax.jit
    def run(x):                      # named like the served superstep
        for _ in range(4):
            x = jnp.sin(x) @ x * 0.5
        return x

    @jax.jit
    def apply(x):
        return x + 1.0

    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((run(x), apply(x)))        # compile first
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("hop.fold"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("hop.compute"):
            jax.block_until_ready(run(x))
        with jax.profiler.TraceAnnotation("hop.ship"):
            jax.block_until_ready(apply(x))
            time.sleep(0.01)
        time.sleep(0.005)                              # under no span
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print("trace:", path, os.path.getsize(path), "bytes")
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print("   line", repr(line.name), len(evs), "events;",
                  names[:12])
            for e in evs[:2]:
                print("      ", e.name, e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:8]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/recorded"))
