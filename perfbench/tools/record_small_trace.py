#!/usr/bin/env python3
"""Record the small trace that tests/perfbench checks the reduction
against (perfbench/testdata/v5e_small.xplane.pb), on a TPU:

    python3 perfbench/tools/record_small_trace.py <out dir>

Two named programs run a few times under "Scheduling:<phase>"
TraceAnnotations, with sleeps between them so that there are idle gaps
to attribute.  Writes the xplane file and, beside it, the reduction's
numbers as ``v5e_small.expected.json``: commit both after looking at the
trace by hand (tools/dump_xplane.py).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from perfbench.lib import xplane
    if jax.devices()[0].platform != "tpu":
        print("record_small_trace: no TPU", file=sys.stderr)
        return 2
    out = argv[1]
    os.makedirs(out, exist_ok=True)

    def schedule_gang_small(x):
        return jnp.tanh(x @ x).sum()

    def apply_delta_small(x):
        return (x + 1.0).sum()
    f = jax.jit(schedule_gang_small)
    g = jax.jit(apply_delta_small)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    log_dir = os.path.join(out, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("Scheduling:prepare"):
            time.sleep(0.002)
            g(x).block_until_ready()
        with jax.profiler.TraceAnnotation("Scheduling:dispatch"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("Scheduling:commit"):
            time.sleep(0.003)
        time.sleep(0.001)
    jax.profiler.stop_trace()
    src = xplane.find_trace(log_dir)
    dst = os.path.join(out, "v5e_small.xplane.pb")
    shutil.copy(src, dst)
    summary = xplane.summarize(xplane.load(dst))
    with open(os.path.join(out, "v5e_small.expected.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(os.path.getsize(dst), "bytes", json.dumps(summary)[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
