"""How ``testdata/small.xplane.pb`` was made (run on the chip, by hand:
``chiprun -- python benchmarks/tests/record_trace.py``): three launches
of a small scan with sleeps between them, traced without the Python
tracer.  Prints the planes and lines it found, and what the reduction
makes of them, and leaves the trace under ``chiprun_out/testdata/``."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce  # noqa: E402


def main() -> None:
    out = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                       "chiprun_out", "testdata")
    shutil.rmtree(out, ignore_errors=True)

    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.sin(c) @ c, None
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((512, 512), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        step(x).block_until_ready()
        time.sleep(0.005)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind,
          "bytes", os.path.getsize(path), "window_s", window_s)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            print("PLANE", plane.name, "LINE", line.name, len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]])
    print("REDUCED", trace_reduce.reduce(trace_reduce.load(out), window_s))


if __name__ == "__main__":
    main()
