"""Record the small profiler trace the trace-reduction test reads.

    python bench/record_trace.py OUT.xplane.pb

On every chip of the host: a few steps of a jitted matmul chain whose
result is summed across the chips (an all-reduce where there are several),
with the harness's span names around them, and a 0.2 s host wait inside a
``bench.host_wait`` span during which the chips are idle.  Writes the
trace's ``.xplane.pb`` to OUT and prints what the test expects of it.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

HOST_WAIT_S = 0.2


def main(argv=None) -> None:
    out = (argv or sys.argv[1:])[0]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import trace

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("d",))
    x = jax.device_put(jnp.ones((len(devs) * 1024, 1024), jnp.float32),
                       NamedSharding(mesh, P("d")))
    w = jax.device_put(jnp.full((1024, 1024), 1e-3, jnp.float32),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x, jnp.sum(x)          # the sum all-reduces across chips

    step(x, w)[1].block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="bench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                x, s = step(x, w)
                s.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(HOST_WAIT_S)
        with jax.profiler.TraceAnnotation("bench.step"):
            x, s = step(x, w)
            s.block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(log_dir)
    shutil.copy(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    summary = trace.reduce(out)
    print(f"{out}: {os.path.getsize(out)} B, {len(devs)} devices "
          f"({devs[0].device_kind}); window {summary.window_s:.6f} s, busy "
          f"{summary.busy_s}, idle by "
          f"span {summary.gaps}, top ops "
          f"{sorted(summary.ops.items(), key=lambda kv: -kv[1])[:8]}")


if __name__ == "__main__":
    main()
