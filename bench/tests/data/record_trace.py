#!/usr/bin/env python3
"""Record the small TPU profiler trace that `test_trace.py` reduces.

    python3 bench/tests/data/record_trace.py <out.xplane.pb>

Run it on a TPU.  It traces three steps of a small jitted program inside a
`bench.window` span, with `bench.step` and `bench.submit` spans around the
steps and the host pauses between them, copies the `.xplane.pb` to
`<out>`, and prints the planes, lines and the reduction.
"""
import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchlib import trace  # noqa: E402


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            with trace.span("submit"):
                time.sleep(0.002)
            with trace.span("step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, out)
    shutil.rmtree(d)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(plane.name, lines)
    print(trace.reduce_file(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
