"""Record ``cpu_trace.xplane.pb``, the small trace the reduction's tests
read: three questions inside ``bench.window``, each a jitted call, a
5 ms host sleep inside ``bench.host_prep``, and another jitted call.

    cd bench/testdata && JAX_PLATFORMS=cpu python record_cpu_trace.py <out dir>

With the Python tracer and the HLO protos off, and the script given by a
relative path, the trace names no source file.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str):
    jax.config.update("jax_enable_x64", True)
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    g = jax.jit(lambda x: jnp.cumsum(x, axis=0))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    g(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.question"):
                f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.host_prep"):
                    time.sleep(0.005)
                g(x).block_until_ready()
    jax.profiler.stop_trace()
    print(sorted(Path(out).rglob("*.xplane.pb"))[-1])


if __name__ == "__main__":
    main(sys.argv[1])
