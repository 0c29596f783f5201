"""One run of one cell: set-up, warm-up, the measured window, the traced
sub-window, the correctness checks, the result line.

One process, wired the way ``python -m kubetpu`` wires it: ClusterStore ->
Scheduler -> SchedulerServer -> Scheduler.run(), async binding.  The
client (client.py) is this benchmark's own thread.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Tuple

from . import client as _client
from . import spec as _spec
from . import stats, traffic as _traffic, world, xplane

# the profiler traces this many seconds from a quarter into the window
# (2 MB of trace a second in the first cell): enough for some cycles of
# the slowest cell, short enough to read back in seconds
PROFILE_SECONDS = 6.0
SCRATCH = os.path.join("perfbench", ".scratch")     # inside the checkout


class RunError(Exception):
    """The run cannot give a result (no chip, warm-up never settled...)."""


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu and (info["platform"] != "tpu" or len(devices) < chips):
        raise RunError(f"cell needs {chips} TPU chip(s); jax found "
                       f"{info['count']} x {info['platform']}")
    return info


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def scheduler_seed(seed: int) -> int:
    """The scheduler folds its seed into a PRNGKey and counts up from it:
    keep it, and the counter, inside 31 bits."""
    return int(seed) % (2 ** 31 - 2 ** 24)


def _join_background_prewarm(timeout: float) -> None:
    for t in threading.enumerate():
        if t.name == "kubetpu-prewarm-ladder":
            t.join(timeout)
            if t.is_alive():
                raise RunError("background prewarm still compiling after "
                               f"{timeout} s")


class Warmup:
    """Decides when set-up is over: the steady population is reached and
    nothing has compiled for a while."""

    def __init__(self, cl: _client.Client, traffic: Dict[str, Any],
                 compile_count: Callable[[], int], out, surge: int = 0):
        w = traffic.get("warmup", {})
        self.cl = cl
        self.compile_count = compile_count
        self.out = out
        self.min_s = float(w.get("min_s", 2.0))
        self.quiet_s = float(w.get("quiet_s", 2.0))
        self.quiet_binds = int(w.get("quiet_binds", 0))
        self.max_s = float(w.get("max_s", 900.0))
        self.dips = int(w.get("dips", 0))
        self.surge = int(surge)
        self.need_bound = cl.resident_bound + int(traffic["depth"])

    def run(self) -> None:
        cl = self.cl
        t_start = cl.clock()
        last_n = self.compile_count()
        last_t, last_bound = t_start, cl.bound_count()
        dips_left, next_dip = self.dips, self.need_bound
        cl.surge(self.surge)
        while True:
            time.sleep(0.05)
            if cl.error is not None:
                raise cl.error
            now, n, bound = cl.clock(), self.compile_count(), cl.bound_count()
            if n != last_n:
                last_n, last_t, last_bound = n, now, bound
            if dips_left and bound >= next_dip:
                cl.dip()
                dips_left -= 1
                next_dip = bound + 3 * cl.resident_bound
                last_t, last_bound = now, bound
                continue
            if (not dips_left and bound >= next_dip
                    and now - t_start >= self.min_s
                    and now - last_t >= self.quiet_s
                    and bound - last_bound >= self.quiet_binds):
                self.out(f"warm-up: {now - t_start:.1f} s, {bound} bound, "
                         f"{n} programs compiled or loaded so far")
                return
            if now - t_start > self.max_s:
                raise RunError(
                    f"warm-up did not settle in {self.max_s} s: {bound} "
                    f"bound (need {self.need_bound}), {cl.pending_count()} "
                    f"pending, last compile {now - last_t:.1f} s ago")


def _start_profiler(log_dir: str) -> None:
    import jax
    from kubetpu.utils import trace as utrace
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the Python tracer slows every call
    opts.host_tracer_level = 2       # TraceAnnotations of the Trace phases
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    # what kubetpu.utils.trace.capture_device_trace sets: with it every
    # Trace phase opens a TraceAnnotation.  capture_device_trace itself
    # takes no profiler options (PERF.md, Open questions).
    utrace._PROFILE_ACTIVE = True


def _stop_profiler() -> None:
    import jax
    from kubetpu.utils import trace as utrace
    utrace._PROFILE_ACTIVE = False
    jax.profiler.stop_trace()


def _sleep_until(t: float, clock) -> None:
    while True:
        d = t - clock()
        if d <= 0:
            return
        time.sleep(min(d, 0.25))


def run_cell(cell: _spec.Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             out: Callable[[str], None] = print) -> Dict[str, Any]:
    """Run the cell once.  Returns the result line as a dict."""
    seconds = float(seconds)
    device = device_info(cell.chips, require_tpu)
    out(f"device: {json.dumps(device)}  cell: {cell.name}  seed: {seed}  "
        f"seconds: {seconds:g}  trace: {int(trace)}")
    from kubetpu.scheduler import Scheduler
    from kubetpu.server import SchedulerServer
    from kubetpu.utils import trace as utrace
    from kubetpu.utils.metrics import SchedulerMetrics
    from kubetpu.utils.sanitize import (install_compile_timer,
                                        install_compile_watchdog)
    timer = install_compile_timer()
    watchdog = install_compile_watchdog()
    config, traffic = cell.config, cell.traffic
    clock = time.perf_counter

    # ---- the world, from the seed
    t_w = clock()
    nodes = world.node_records(config)
    init = world.init_records(config, seed)
    store = world.build_store(nodes, init)
    warm_guess = float(traffic.get("warmup", {}).get("pool_s", 10.0))
    pool = _client.PodPool(
        lambda i: world.measured_record(config, _client.ROLE, i),
        world.api_pod, _traffic.pool_size(traffic, seconds, warm_guess))
    out(f"world: {len(nodes)} nodes, {len(init)} init pods, "
        f"{len(pool.records)} measured pods built in {clock() - t_w:.2f} s")
    # the pod pool is this benchmark's, not the scheduler's: keep its
    # million objects out of every later garbage collection
    gc.collect()
    gc.freeze()

    flight = None
    if trace:
        flight = utrace.arm_flight_recorder(capacity=16384,
                                            max_spans_per_cycle=64)

    # ---- the serving path
    cl = _client.Client(store, traffic, pool, clock)
    sched = Scheduler(store, config=world.scheduler_config(
        config["scheduler"], config.get("mesh_shape")),
        metrics=SchedulerMetrics(), seed=scheduler_seed(seed),
        async_binding=True)
    server = SchedulerServer(sched, port=0)
    server.start()
    try:
        t_p = clock()
        sched.run()
        _join_background_prewarm(900.0)
        out(f"prewarm: {clock() - t_p:.2f} s")
        cl.start()
        Warmup(cl, traffic, watchdog.compile_count, out,
               surge=config.get("warmup", {}).get("surge", 0)).run()

        # ---- the window
        wall_offset = utrace.wallclock() - clock()
        programs0 = dict(watchdog.counts)
        t0 = clock() + 0.05
        setup_s = process_age_s() + 0.05
        out(f"window: starts, setup_s {setup_s:.3f}  compile so far "
            f"{json.dumps(timer.snapshot())}")
        trace_dir = os.path.join(cell.root, SCRATCH, "trace")
        if trace:
            span = min(PROFILE_SECONDS, seconds / 2.0)
            _sleep_until(t0 + seconds / 4.0, clock)
            _start_profiler(trace_dir)
            _sleep_until(t0 + seconds / 4.0 + span, clock)
            _stop_profiler()
        _sleep_until(t0 + seconds, clock)
        window_compiles = 0
        for key, n in dict(watchdog.counts).items():
            if n != programs0.get(key, 0):
                window_compiles += n - programs0.get(key, 0)
                out(f"compiled or loaded inside the window: {key[0]} "
                    f"...{key[1][-1100:]}")
        cl.stop_offering()
        peak = memory_peak_bytes()
        # the scheduler stops first, the client after it: every bind the
        # store announces reaches the client's log
        sched.close()
        sched.wait_for_inflight_binds(timeout=30.0)
        cl.stop()
        cycles = ([c.to_dict() for c in flight.cycles()]
                  if flight is not None else [])
    finally:
        sched.close()
        server.stop()
        if trace:
            utrace.disarm_flight_recorder()
    if sched.recovery_log:
        out(f"recovery_log: {list(sched.recovery_log)[:3]}")

    # ---- end-to-end metrics, from the client's stamps alone
    e2e, attempted, failed, stuck = end_to_end(cell, cl, t0, seconds)
    e2e["setup_s"] = setup_s
    out(f"client: offered {len(cl.order)}, bound {cl.bound_count()}, "
        f"pods built late {pool.built_late}, window compiles "
        f"{window_compiles}")

    # ---- per-layer metrics
    per_layer: Dict[str, float] = {}
    breakdown = None
    device_extra: Dict[str, float] = {}
    if trace:
        if device["platform"] == "tpu":
            summary = xplane.summarize(
                xplane.load(xplane.find_trace(trace_dir)))
        else:       # a rehearsal off the chip has no device plane to read
            summary = {"window_s": 0.0, "busy_s": 0.0, "ops": [],
                       "modules": {}, "idle_gaps": []}
        device_extra = {"busy_s": summary["busy_s"],
                        "window_s": summary["window_s"]}
        breakdown = {"device_ops": [[n[:96], s] for n, s in summary["ops"]],
                     "idle_gaps": [[n[:96], s]
                                   for n, s in summary["idle_gaps"]]}
        in_window = [c for c in cycles
                     if t0 <= c["t0"] - wall_offset < t0 + seconds]
        ctx = SimpleNamespace(
            cell=cell, seconds=seconds, t0=t0, client=cl, cycles=in_window,
            trace=summary,
            window_compiles=window_compiles, device=device,
            n_nodes=len(nodes), resident_pods=len(init) + cl.resident_bound)
        for name, read in cell.readers().items():
            value = read(ctx)
            if value is not None:
                per_layer[name] = float(value)

    # ---- correct
    from . import check
    programs1 = dict(watchdog.counts)
    ok, lines = check.decide(cell, seed, nodes, init, pool.records, cl,
                             store, stuck)
    for line in lines:
        out(line)
    # check (b) is to drive the window's own programs: say which it had
    # to compile or load that this process had not run before
    fresh = sorted({key[0] for key, n in dict(watchdog.counts).items()
                    if n != programs1.get(key, 0)})
    out(f"check (b) compiled or loaded {len(fresh)} programs the run had "
        f"not used before: {fresh}")

    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    values = per_layer if trace else e2e
    result = {
        "correct": bool(ok), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if k in units},
        "device": dict(device, memory_peak_bytes=peak, **device_extra),
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if trace:
        out("end-to-end in this traced run (not reported): "
            + json.dumps(e2e))
    return result


def end_to_end(cell: _spec.Cell, cl: _client.Client, t0: float,
               seconds: float) -> Tuple[Dict[str, float], int, int,
                                        List[str]]:
    """The cell's end-to-end metrics from the client's stamps, with
    (attempted, failed, names of the pods given up on)."""
    n = stats.in_window(cl.bound_t.values(), t0, seconds)
    # a pod is given up on when two batches' worth of pods offered after
    # it are bound and it is not: the queue is first in, first out, so
    # it was passed over
    slack = 2 * int(cell.config["scheduler"]["batch_size"])
    bound_idx = [i for i, name in enumerate(cl.order)
                 if name in cl.bound_t]
    last = bound_idx[-1] if bound_idx else -1
    stuck = [name for i, name in enumerate(cl.order[:max(last, 0)])
             if name not in cl.bound_t and i < last - slack]
    return ({"pods_bound_per_s": n / seconds}, n + len(stuck), len(stuck),
            stuck)
