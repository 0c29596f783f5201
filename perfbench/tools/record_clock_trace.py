#!/usr/bin/env python3
"""Record the small trace that tests/perfbench checks the clock offset
and ``readback_wake`` against (perfbench/testdata/v5e_clock.*), on a TPU:

    python3 perfbench/tools/record_clock_trace.py <out dir>

A few cycles of the PROGRAM'S OWN tracing (kubetpu/utils/trace.py: the
flight recorder armed, ``capture_device_trace`` with profiler options,
a ``Trace`` with its phases) around a small jitted program whose name
contains ``schedule_gang``, so the capture holds what a ``--trace 1`` run
holds: one ``Scheduling:<phase>`` annotation per open phase, one
``kubetpu.clock`` event a cycle, the program on the device plane.  Writes

    v5e_clock.xplane.pb        the capture
    v5e_clock.cycles.json      the cycles' records (CycleRecord.to_dict)
    v5e_clock.expected.json    what perfbench/lib/spans.py and
                               lib/xplane.py reduce the two to

Commit all three after looking at the trace by hand (tools/dump_xplane.py).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CYCLES = 4


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from kubetpu.utils import trace as utrace
    from perfbench.lib import spans, xplane
    if jax.devices()[0].platform != "tpu":
        print("record_clock_trace: no TPU", file=sys.stderr)
        return 2
    out = argv[1]
    os.makedirs(out, exist_ok=True)

    def schedule_gang_small(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x.sum(axis=0)[:16]
    f = jax.jit(schedule_gang_small)
    x = jnp.ones((1024, 1024), jnp.float32)
    np.asarray(f(x))
    log_dir = os.path.join(out, "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        with utrace.capture_device_trace(log_dir, profiler_options=opts):
            for _ in range(CYCLES):
                pop = utrace.begin_pop()
                time.sleep(0.001)
                pop.close()
                tr = utrace.Trace(utrace.CYCLE_TRACE, pop=pop, pods=16)
                tr.phase("snapshot")
                time.sleep(0.001)
                tr.phase("tensorize")
                time.sleep(0.002)
                with tr.phase("dispatch"):
                    res = f(x)
                with tr.phase("packed-readback", ann="readback"):
                    np.asarray(res)
                with tr.phase("commit"):
                    time.sleep(0.003)
                tr.finish()
        cycles = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    src = xplane.find_trace(log_dir)
    dst = os.path.join(out, "v5e_clock.xplane.pb")
    shutil.copy(src, dst)
    pd = xplane.load(dst)
    summary = xplane.summarize(pd)
    expected = {"clock": spans.clock_offset(pd),
                "readback_wake_ms": spans.readback_wake_ms(cycles, pd),
                "idle_gaps": summary["idle_gaps"],
                "window_s": summary["window_s"],
                "busy_s": summary["busy_s"]}
    with open(os.path.join(out, "v5e_clock.cycles.json"), "w") as fh:
        json.dump(cycles, fh)
    with open(os.path.join(out, "v5e_clock.expected.json"), "w") as fh:
        json.dump(expected, fh, indent=1)
    print(os.path.getsize(dst), "bytes", json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
