"""kubelint self-tests: every rule family fires on a known-bad snippet,
stays quiet on the matching known-good one, the suppression syntax works,
and — the tier-1 gate — the shipped ``kubetpu/`` tree is clean (every
remaining finding carries an inline suppression with a reason)."""

import json
import os
import subprocess
import sys

import pytest

from tools.kubelint import run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_snippet(tmp_path, src, rules=None):
    f = tmp_path / "snippet.py"
    f.write_text(src)
    return run_lint([str(f)], root=str(tmp_path), rules=rules)


def rule_ids(result):
    return sorted({f.rule for f in result.findings})


# ---------------------------------------------------------------------------
# host-sync family


HOST_SYNC_BAD = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def kernel(x, y):
    v = float(x)                 # cast on a possible tracer
    s = jnp.sum(x)
    if s > 0:                    # branch on a tracer
        y = y + 1
    w = s.item()                 # device sync
    h = np.asarray(x)            # host materialization
    return v + w + h
"""

HOST_SYNC_GOOD = """
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("k",))
def kernel(x, k):
    n = x.shape[0]               # shapes are static under jit
    v = float(k)                 # static_argnames param: fine
    m = float(len(x))            # len() is static
    if k > 2:                    # static branch
        x = x * v
    return jnp.where(x > 0, x, m) * n
"""


def test_host_sync_fires_on_bad(tmp_path):
    res = lint_snippet(tmp_path, HOST_SYNC_BAD)
    ids = rule_ids(res)
    assert "host-sync/cast" in ids
    assert "host-sync/traced-branch" in ids
    assert "host-sync/item" in ids
    assert "host-sync/asarray" in ids


def test_host_sync_quiet_on_good(tmp_path):
    res = lint_snippet(tmp_path, HOST_SYNC_GOOD, rules=["host-sync"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


def test_traced_closure_reaches_helpers(tmp_path):
    """A helper is traced because a jitted function calls it — the rule
    fires inside the helper even though it has no decorator."""
    src = """
import jax

def helper(x):
    return float(x) + 1.0

@jax.jit
def entry(x):
    return helper(x)
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert any(f.rule == "host-sync/cast" and "helper" in f.message
               for f in res.findings)


def test_scan_body_is_traced(tmp_path):
    """Functions handed to lax.scan/while_loop are roots too."""
    src = """
import jax
import jax.numpy as jnp

def run(xs):
    def step(carry, x):
        bad = int(x)
        return carry + bad, x
    return jax.lax.scan(step, 0.0, xs)
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert any(f.rule == "host-sync/cast" for f in res.findings)


def test_loop_readback_fires(tmp_path):
    src = """
import jax

@jax.jit
def program(x):
    return x * 2

def drain(x, n):
    res = program(x)
    out = []
    for i in range(n):
        out.append(float(res[i]))
    return out
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert any(f.rule == "host-sync/loop-readback" for f in res.findings)


def test_loop_readback_quiet_after_asarray(tmp_path):
    src = """
import jax
import numpy as np

@jax.jit
def program(x):
    return x * 2

def drain(x, n):
    res = np.asarray(program(x))
    return [float(res[i]) for i in range(n)]
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


# ---------------------------------------------------------------------------
# recompile family


def test_jit_in_body_fires(tmp_path):
    src = """
import jax

def serve(xs):
    out = []
    for x in xs:
        f = jax.jit(lambda v: v + 1)
        out.append(f(x))
    return out
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert any(f.rule == "recompile/jit-in-body" for f in res.findings)


def test_jit_decorator_quiet(tmp_path):
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k",))
def f(x, k=3):
    return x * k

g = jax.jit(f)
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


def test_nonhashable_static_fires(tmp_path):
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg=None):
    return x

def call(x):
    return f(x, cfg=["a", "b"])
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert any(f.rule == "recompile/nonhashable-static"
               for f in res.findings)


def test_nonhashable_static_default_fires(tmp_path):
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg=[1, 2]):
    return x
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert any(f.rule == "recompile/nonhashable-static"
               for f in res.findings)


def test_unbucketed_static_fires_and_pow2_quiet(tmp_path):
    src = """
import functools
import jax

def pow2_bucket(n, minimum=8):
    cap = minimum
    while cap < n:
        cap *= 2
    return cap

@functools.partial(jax.jit, static_argnames=("pad_to",))
def grow(x, pad_to=0):
    return x

def bad(x, items):
    return grow(x, pad_to=len(items))

def good(x, items):
    return grow(x, pad_to=pow2_bucket(len(items)))
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    unbucketed = [f for f in res.findings
                  if f.rule == "recompile/unbucketed-static"]
    assert len(unbucketed) == 1  # only the bad() call site


def test_positional_static_arg_checked(tmp_path):
    """Static-arg hygiene applies to positional spellings too."""
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("cfg",))
def f(x, cfg=None):
    return x

def call(x):
    return f(x, ["a", "b"])
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert any(f.rule == "recompile/nonhashable-static"
               for f in res.findings)


def test_call_form_jit_captures_static_params(tmp_path):
    """f = jax.jit(g, static_argnames=...) marks g's static params, so a
    float() on one is NOT a host-sync finding."""
    src = """
import jax

def g(x, n):
    return x * float(n)

run = jax.jit(g, static_argnames=("n",))
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


def test_shape_branch_fires(tmp_path):
    src = """
import jax

def bound():
    return 7

@jax.jit
def f(x):
    if x.shape[0] > bound():
        return x * 2
    return x
"""
    res = lint_snippet(tmp_path, src, rules=["recompile"])
    assert any(f.rule == "recompile/shape-branch" for f in res.findings)


# ---------------------------------------------------------------------------
# numeric family


def test_numeric_f64_fires(tmp_path):
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return x.astype(jnp.float64)
"""
    res = lint_snippet(tmp_path, src, rules=["numeric"])
    assert any(f.rule == "numeric/f64" for f in res.findings)


def test_numeric_floor_div_fires(tmp_path):
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def f(a, b):
    return jnp.floor(a / b)
"""
    res = lint_snippet(tmp_path, src, rules=["numeric"])
    assert any(f.rule == "numeric/floor-div" for f in res.findings)


def test_numeric_score_div_fires(tmp_path):
    src = """
import jax
import jax.numpy as jnp

MAX_NODE_SCORE = 100.0

@jax.jit
def f(raw, max_c):
    return MAX_NODE_SCORE * raw / max_c
"""
    res = lint_snippet(tmp_path, src, rules=["numeric"])
    assert any(f.rule == "numeric/score-div" for f in res.findings)


def test_numeric_x64_fires(tmp_path):
    src = """
import jax
jax.config.update("jax_enable_x64", True)
"""
    res = lint_snippet(tmp_path, src, rules=["numeric"])
    assert any(f.rule == "numeric/x64-enable" for f in res.findings)


def test_numeric_quiet_on_idiv_style(tmp_path):
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def _idiv_like(a, b):
    q = a * (1.0 / b)
    return jnp.floor(q + 0.5)
"""
    res = lint_snippet(tmp_path, src, rules=["numeric"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


# ---------------------------------------------------------------------------
# purity family


def test_purity_env_fires_in_kernel_module(tmp_path):
    src = """
import os
import jax

SCALE = float(os.environ.get("SCALE", "1.0"))

@jax.jit
def f(x):
    return x * SCALE
"""
    res = lint_snippet(tmp_path, src, rules=["purity"])
    assert any(f.rule == "purity/env-access" for f in res.findings)


def test_purity_global_mutation_fires(tmp_path):
    src = """
import jax

_CACHE = {}

@jax.jit
def f(x):
    return x

def helper(k, v):
    global _COUNT
    _COUNT = 1
    _CACHE[k] = v
    _CACHE.update({k: v})
"""
    res = lint_snippet(tmp_path, src, rules=["purity"])
    kinds = [f.message for f in res.findings
             if f.rule == "purity/global-mutate"]
    assert len(kinds) >= 2  # global stmt + container mutation


def test_purity_quiet_without_jit(tmp_path):
    """A module with no jit roots (and outside ops/models) is not a kernel
    module — env access there is framework/config code, not kernel code."""
    src = """
import os

def configure():
    return os.environ.get("MODE", "default")
"""
    res = lint_snippet(tmp_path, src, rules=["purity"])
    assert res.clean


# ---------------------------------------------------------------------------
# concurrency family


CONCURRENCY_BAD_UNGUARDED = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def put(self, k, v):
        with self._lock:
            self.items[k] = v

    def drop(self, k):
        self.items.pop(k, None)     # mutation without the lock
"""


def test_unguarded_write_fires(tmp_path):
    res = lint_snippet(tmp_path, CONCURRENCY_BAD_UNGUARDED,
                       rules=["concurrency"])
    assert any(f.rule == "concurrency/unguarded-access"
               and "items" in f.message for f in res.findings), \
        "\n".join(str(f) for f in res.findings)


def test_unguarded_read_fires(tmp_path):
    src = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def put(self, k, v):
        with self._lock:
            self.items[k] = v

    def size(self):
        return len(self.items)      # read without the lock
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert any(f.rule == "concurrency/unguarded-access"
               and "read" in f.message for f in res.findings)


def test_locked_helper_quiet(tmp_path):
    """A private helper whose every call site holds the lock is analyzed
    as entered with it held — no finding."""
    src = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def put(self, k, v):
        with self._lock:
            self._store(k, v)

    def replace(self, k, v):
        with self._lock:
            self._store(k, v)

    def _store(self, k, v):
        self.items[k] = v
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert res.clean, "\n".join(str(f) for f in res.findings)


def test_helper_reachable_without_lock_fires(tmp_path):
    """One lock-free call site poisons the helper's entry set: its
    guarded accesses become reachable from a thread entry point."""
    src = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = {}

    def put(self, k, v):
        with self._lock:
            self._store(k, v)

    def put_fast(self, k, v):
        self._store(k, v)           # bypasses the lock

    def _store(self, k, v):
        self.items[k] = v
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert any(f.rule == "concurrency/unguarded-access"
               for f in res.findings)


def test_guarded_by_annotation_and_optout(tmp_path):
    """Explicit guarded-by() declares ownership inference can't see;
    guarded-by(none) opts a deliberately unguarded attribute out."""
    src = """
import threading

class Box:
    def __init__(self):
        self._mu = threading.Lock()
        self.store = Ext()  # kubelint: guarded-by(_mu)
        self.flag = {}  # kubelint: guarded-by(none)

    def read(self):
        return self.store           # declared guarded: fires

    def poke(self):
        with self._mu:
            self.flag["x"] = 1

    def poke_free(self):
        self.flag["x"] = 2          # opted out: quiet


class Ext:
    pass
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    msgs = [f.message for f in res.findings
            if f.rule == "concurrency/unguarded-access"]
    assert any("store" in m and "declared" in m for m in msgs), msgs
    assert not any("flag" in m for m in msgs), msgs


def test_lock_order_cycle_fires(tmp_path):
    src = """
import threading

class AB:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._b:
            with self._a:
                pass
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert any(f.rule == "concurrency/lock-order"
               and "cycle" in f.message for f in res.findings)


def test_lock_order_consistent_quiet(tmp_path):
    src = """
import threading

class AB:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._a:
            with self._b:
                pass
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert not any(f.rule == "concurrency/lock-order"
                   for f in res.findings)


def test_lock_order_cycle_across_classes(tmp_path):
    """The graph follows calls made while holding a lock through
    `self.attr = OtherClass()` bindings."""
    src = """
import threading

class Inner:
    def __init__(self):
        self._ilock = threading.Lock()

    def touch(self):
        with self._ilock:
            pass


class Outer:
    def __init__(self):
        self._olock = threading.Lock()
        self.inner = Inner()

    def forward(self):
        with self._olock:
            self.inner.touch()

    def backward(self):
        # Inner._ilock -> Outer._olock: closes the cycle
        with self.inner._ilock:
            with self._olock:
                pass
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert any(f.rule == "concurrency/lock-order"
               and "cycle" in f.message for f in res.findings), \
        "\n".join(str(f) for f in res.findings)


def test_blocking_sleep_under_lock_fires(tmp_path):
    src = """
import threading
import time

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def nap(self):
        with self._lock:
            time.sleep(0.5)
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    assert any(f.rule == "concurrency/blocking-under-lock"
               for f in res.findings)


def test_device_dispatch_under_lock_fires(tmp_path):
    """jit-root calls and .tolist() readbacks under a lock are the
    convoy shape the chain/pipeline regression smells of."""
    src = """
import threading
import jax

@jax.jit
def program(x):
    return x * 2

class S:
    def __init__(self):
        self._chain_lock = threading.Lock()

    def dispatch(self, x):
        with self._chain_lock:
            res = program(x)
            return res.tolist()
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    msgs = [f.message for f in res.findings
            if f.rule == "concurrency/blocking-under-lock"]
    assert any("jitted program" in m for m in msgs), msgs
    assert any("tolist" in m for m in msgs), msgs


def test_condition_wait_on_other_lock_fires(tmp_path):
    src = """
import threading

class W:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition()

    def bad_wait(self):
        with self._lock:
            with self._cond:
                self._cond.wait(1.0)   # blocks while _lock is held

    def good_wait(self):
        with self._cond:
            self._cond.wait(1.0)       # only its own lock: idiomatic
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    waits = [f for f in res.findings
             if f.rule == "concurrency/blocking-under-lock"
             and "wait" in f.message]
    assert len(waits) == 1, "\n".join(str(f) for f in res.findings)


def test_orphan_daemon_thread_fires_and_stop_event_quiet(tmp_path):
    src = """
import threading

class Orphan:
    def run(self):
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()

    def _loop(self):
        while True:
            pass


class Stoppable:
    def __init__(self):
        self._stop = threading.Event()

    def run(self):
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()

    def _loop(self):
        while not self._stop.wait(1.0):
            pass

    def close(self):
        self._stop.set()
"""
    res = lint_snippet(tmp_path, src, rules=["concurrency"])
    orphans = [f for f in res.findings
               if f.rule == "concurrency/orphan-daemon-thread"]
    assert len(orphans) == 1
    assert "Orphan" in orphans[0].message


def test_lock_graph_cli(tmp_path):
    """--lock-graph renders the ownership map the README embeds."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kubelint", "kubetpu/", "--lock-graph"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "SchedulerCache" in proc.stdout
    assert "SchedulingQueue._cond" in proc.stdout
    assert "PodNominator._lock" in proc.stdout


# ---------------------------------------------------------------------------
# suppression machinery


def test_suppression_with_reason_suppresses(tmp_path):
    src = """
import jax

@jax.jit
def f(x, w):
    return x * float(w)  # kubelint: ignore[host-sync/cast] w is static here
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert res.clean
    assert any(f.rule == "host-sync/cast" and f.suppressed
               for f in res.suppressed)


def test_suppression_without_reason_is_a_finding(tmp_path):
    src = """
import jax

@jax.jit
def f(x, w):
    return x * float(w)  # kubelint: ignore[host-sync/cast]
"""
    res = lint_snippet(tmp_path, src)
    assert any(f.rule == "kubelint/bad-suppression" for f in res.findings)
    # the underlying finding is NOT suppressed by a reason-less comment
    assert any(f.rule == "host-sync/cast" for f in res.findings)


def test_suppression_wrong_rule_does_not_mask(tmp_path):
    src = """
import jax

@jax.jit
def f(x, w):
    return x * float(w)  # kubelint: ignore[numeric/f64] wrong family
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert any(f.rule == "host-sync/cast" for f in res.findings)


def test_unused_suppression_is_reported(tmp_path):
    src = """
import jax

@jax.jit
def f(x):
    return x + 1  # kubelint: ignore[host-sync/cast] nothing to suppress here
"""
    res = lint_snippet(tmp_path, src)
    assert any(f.rule == "kubelint/unused-suppression"
               for f in res.findings)


def test_loop_readback_not_hidden_by_later_launder(tmp_path):
    """Laundering a name to host AFTER the loop must not hide the
    per-element sync inside it (flow-sensitive device map)."""
    src = """
import jax
import numpy as np

@jax.jit
def program(x):
    return x * 2

def drain(x, n):
    res = program(x)
    total = 0.0
    for i in range(n):
        total += float(res[i])
    res = np.asarray(res)
    return total, res
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert any(f.rule == "host-sync/loop-readback" for f in res.findings)


def test_standalone_suppression_covers_next_line(tmp_path):
    src = """
import jax

@jax.jit
def f(x, w):
    # kubelint: ignore[host-sync/cast] w is a static weight
    return x * float(w)
"""
    res = lint_snippet(tmp_path, src, rules=["host-sync"])
    assert res.clean


# ---------------------------------------------------------------------------
# CLI + JSON mode


def test_cli_json_mode(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text("""
import jax

@jax.jit
def f(x):
    return float(x)
""")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kubelint", str(f), "--json",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["clean"] is False
    assert any(x["rule"] == "host-sync/cast" for x in doc["findings"])


def test_cli_no_files_is_usage_error(tmp_path):
    """A typo'd path must not let the CI gate go vacuously green."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kubelint",
         str(tmp_path / "no_such_dir")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2
    assert "no Python files" in proc.stderr


def test_package_init_relative_imports_resolve(tmp_path):
    """`from .mod import f` inside pkg/__init__.py resolves against the
    package itself, so kernels re-exported through __init__ stay in the
    traced closure."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "kern.py").write_text("""
def helper(x):
    return float(x)
""")
    (pkg / "__init__.py").write_text("""
import jax
from .kern import helper

@jax.jit
def entry(x):
    return helper(x)
""")
    res = run_lint([str(pkg)], root=str(tmp_path), rules=["host-sync"])
    assert any(f.rule == "host-sync/cast" and "helper" in f.message
               for f in res.findings), \
        "\n".join(str(f) for f in res.findings)


def test_cli_clean_exit_zero(tmp_path):
    f = tmp_path / "ok.py"
    f.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kubelint", str(f), "--json",
         "--root", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["clean"] is True


# ---------------------------------------------------------------------------
# delta family: incremental-tensorization discipline


DELTA_BAD = """
import jax
from kubetpu.state.tensors import SnapshotBuilder


class MiniScheduler:
    def schedule_pending(self):
        return self._prepare()

    def _prepare(self):
        builder = SnapshotBuilder()
        host = builder.build([])
        cluster = host.to_device()
        return jax.device_put(cluster)
"""

DELTA_GOOD = """
from kubetpu.state.tensors import SnapshotBuilder


class MiniScheduler:
    def schedule_pending(self):
        return self._prepare()

    def _prepare(self):
        # the delta path: no rebuild, no upload
        cluster, stats = self._delta.refresh([])
        return cluster

    def resync(self):
        # the blessed resync path may rebuild the world
        builder = SnapshotBuilder()
        return builder.build([]).to_device()

    def prewarm(self):
        # NOT reachable from schedule_pending: out-of-cycle builds are fine
        return SnapshotBuilder().build([]).to_device()
"""


def test_delta_fires_on_cycle_loop_retensorize(tmp_path):
    res = lint_snippet(tmp_path, DELTA_BAD, rules=["delta"])
    assert rule_ids(res) == ["delta/full-retensorize-in-loop"]
    # all three shapes fire: .build(), .to_device(), device_put
    assert len(res.findings) == 3


def test_delta_quiet_on_blessed_resync_and_out_of_cycle(tmp_path):
    res = lint_snippet(tmp_path, DELTA_GOOD, rules=["delta"])
    assert res.clean, [str(f) for f in res.findings]


def test_delta_family_registered():
    from tools.kubelint import RULE_FAMILIES
    assert "delta" in RULE_FAMILIES


# ---------------------------------------------------------------------------
# the real gate: the shipped tree is clean


def test_kubetpu_tree_is_clean():
    res = run_lint([os.path.join(REPO, "kubetpu")], root=REPO)
    assert res.clean, (
        "kubelint findings in kubetpu/ — fix them or add an inline "
        "suppression with a reason:\n"
        + "\n".join(str(f) for f in res.findings))


def test_kubetpu_tree_suppressions_all_carry_reasons():
    res = run_lint([os.path.join(REPO, "kubetpu")], root=REPO)
    for f in res.suppressed:
        assert f.reason.strip(), str(f)


def test_detects_at_least_four_rule_families():
    """Acceptance criterion: >= 4 rule families, each proven to fire by a
    test above; this asserts the registry agrees."""
    from tools.kubelint import RULE_FAMILIES
    assert len(RULE_FAMILIES) >= 4


def test_concurrency_family_registered():
    from tools.kubelint import RULE_FAMILIES
    assert "concurrency" in RULE_FAMILIES


# ---------------------------------------------------------------------------
# exact family: raw collectives + raw tie-argmax (source half of the
# kubeexact exactness contract)


def test_raw_collective_reduce_fires_anywhere(tmp_path):
    src = """
import jax

def auction(scores):
    return jax.lax.psum(scores, "pods")
"""
    res = lint_snippet(tmp_path, src, rules=["exact"])
    assert rule_ids(res) == ["exact/raw-collective-reduce"]
    assert "exact_psum" in res.findings[0].message


def test_raw_collective_quiet_in_blessed_module(tmp_path):
    src = """
import jax

def exact_psum(x, axis):
    return jax.lax.psum(x, axis)
"""
    d = tmp_path / "kubetpu" / "ops"
    d.mkdir(parents=True)
    f = d / "kernels.py"
    f.write_text(src)
    res = run_lint([str(f)], root=str(tmp_path), rules=["exact"])
    assert res.clean, [str(x) for x in res.findings]


def test_raw_tie_argmax_fires_only_in_selection_modules(tmp_path):
    src = """
import jax.numpy as jnp

def pick(scores):
    return jnp.argmax(scores, axis=-1)
"""
    d = tmp_path / "kubetpu" / "parallel"
    d.mkdir(parents=True)
    f = d / "shardmap.py"
    f.write_text(src)
    res = run_lint([str(f)], root=str(tmp_path), rules=["exact"])
    assert rule_ids(res) == ["exact/raw-tie-argmax"]
    # the same argmax in a non-selection module is a local utility
    res = lint_snippet(tmp_path, src, rules=["exact"])
    assert res.clean, [str(x) for x in res.findings]


def test_exact_family_registered():
    from tools.kubelint import RULE_FAMILIES
    assert "exact" in RULE_FAMILIES


def test_exact_rule_modules_exist():
    """The blessed-helper home and every cross-axis selection module the
    exact family names must be files of this tree: a rule keyed on a
    module that was deleted or renamed guards nothing."""
    import importlib.util

    from tools.kubelint import rules_exact
    for name in (rules_exact._BLESSED_MODULE,
                 *rules_exact._SELECTION_MODULES):
        assert importlib.util.find_spec(name) is not None, name


# ---------------------------------------------------------------------------
# per-rule suppression staleness


def test_partially_stale_suppression_is_reported(tmp_path):
    src = """
import jax

@jax.jit
def f(x, w):
    # only host-sync/cast fires below: the numeric/f64 id is dead weight
    return x * float(w)  # kubelint: ignore[host-sync/cast, numeric/f64] static weight
"""
    res = lint_snippet(tmp_path, src)
    stale = [f for f in res.findings
             if f.rule == "kubelint/stale-suppression"]
    assert stale and "numeric/f64" in stale[0].message
    # the live half still suppresses its finding
    assert any(f.rule == "host-sync/cast" for f in res.suppressed)
    assert not any(f.rule == "kubelint/unused-suppression"
                   for f in res.findings)


def test_fully_live_multirule_suppression_is_quiet(tmp_path):
    src = """
import jax
import numpy as np

@jax.jit
def f(x, w):
    # kubelint: ignore[host-sync/cast] w is a static weight
    return x * float(w)
"""
    res = lint_snippet(tmp_path, src)
    assert not any(f.rule in ("kubelint/stale-suppression",
                              "kubelint/unused-suppression")
                   for f in res.findings)
