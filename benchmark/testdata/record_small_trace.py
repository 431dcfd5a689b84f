#!/usr/bin/env python3
"""How benchmark/testdata/small_tpu.xplane.pb was recorded, on one v5e chip:

    python3 benchmark/testdata/record_small_trace.py <outdir>

A few jitted programs inside the window range, with host ranges of known
names around the stretches in which the device has nothing to do, so that
the self-test of benchmark/trace_reduce.py can say which labels the longest
gaps must carry. Prints the reduction; the expected numbers beside the
recorded file were taken from that print.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from benchmark import trace_reduce

    out = sys.argv[1]
    x = jnp.arange(1 << 20, dtype=jnp.float32)
    sort = jax.jit(lambda v: jnp.sort(-v))
    dot = jax.jit(lambda v: (v.reshape(1024, 1024) @ v.reshape(1024, 1024)))
    sort(x).block_until_ready()
    dot(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for i in range(3):
            with jax.profiler.TraceAnnotation("selftest.host_parse[i=%d]" % i):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("selftest.device_step"):
                sort(x).block_until_ready()
                dot(x).block_until_ready()
        with jax.profiler.TraceAnnotation("selftest.host_tail"):
            time.sleep(0.03)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out)
    print(json.dumps({"xplane": path, "bytes": os.path.getsize(path),
                      "device": jax.devices()[0].device_kind}))
    print(json.dumps(trace_reduce.reduce_file(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
