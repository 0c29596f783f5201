"""AOT executable artifacts: serialized-XLA warm start for the scheduler.

Cold start is the production blocker, not steady-state speed: a restart
pays XLA for every ladder program (first_run_s is 133-737 s at the
north-star shapes).  The persistent compilation cache (utils/compilation)
bounds that to a disk load, but a cache-warm restart still pays the full
trace + lower for each program before the cache key can even be computed.
This module removes that too: executables compiled at BUILD/DEPLOY time
(tools/kubeaot) are serialized via ``jax.experimental.serialize_executable``
into a versioned artifact directory, and at serving start the dispatch
seams load them directly — no trace, no lower, no XLA.

Three pieces:

* ``AotStore`` — the artifact directory.  One ``.aotx`` file per compiled
  variant, named by the lowering sha256 + an environment key, plus an
  ``index.json`` mapping runtime signature keys to artifacts.  Artifacts
  are pickles (executable payload + in/out tree defs) and are TRUSTED
  BUILD OUTPUTS — load them only from directories you produced.
* ``AotRuntime`` — the dispatch half.  Armed (``arm()`` /
  ``KUBETPU_AOT_DIR``), the serving seams in models/gang.py,
  models/sequential.py, models/programs.py and scheduler.py route each
  call through ``dispatch()``: a signature hit calls the loaded
  executable (statics dropped — they are baked into the program), a miss
  falls back to the jit exactly as before (the persistent-cache/trace
  ladder).  ``capture`` mode is the build side of the same seam: instead
  of calling the jit it runs ``jit.lower(...).compile()``, serializes the
  result, and registers it — so captured call forms are byte-identical
  to the serving call forms by construction.
* Artifact KEYS.  An artifact's identity is its build-time lowering
  sha256 (the census manifest's canonical hash) + (jax/jaxlib version,
  backend, device/topology signature).  The RUNTIME lookup key adds
  nothing that needs a trace: (program, static signature, call treedef,
  flattened avals), plus an index-level environment check that includes a
  digest of the kernel source tree — a kernel edit, jaxlib bump, backend
  or topology change all invalidate every artifact and the seams fall
  back per bucket to the persistent-cache/trace path.

Disarmed (the default) the seams add one module-attribute read per
dispatch — the hot path is otherwise untouched, mirroring the flight
recorder's arming contract (trace.py).
"""

from __future__ import annotations

import hashlib
import inspect
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

LOG = logging.getLogger("kubetpu.aot")

DIR_ENV = "KUBETPU_AOT_DIR"
INDEX_NAME = "index.json"
INDEX_COMMENT = ("AOT executable artifact index (tools/kubeaot). "
                 "Regenerate: make aot. ci_lint.sh fails when the census-"
                 "family rows drift from COMPILE_MANIFEST.json.")

# the kernel source surface an artifact's program is compiled from: any
# edit here must invalidate every artifact (the lowering would change in
# ways the signature key cannot see)
_KERNEL_PATHS = ("models", "ops", "state", "preemption.py", "parallel")


# ------------------------------------------------------------ environment


def _pkg_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_kernel_digest: Optional[str] = None
_kernel_digest_lock = threading.Lock()


def kernel_digest() -> str:
    """sha256 over the kernel source files (kubetpu/models, ops, state,
    parallel, preemption.py) — the cheap no-trace staleness fence: a
    kernel edit changes the digest, which mismatches every artifact
    index built before it."""
    global _kernel_digest
    with _kernel_digest_lock:
        if _kernel_digest is not None:
            return _kernel_digest
        h = hashlib.sha256()
        root = _pkg_root()
        for rel in _KERNEL_PATHS:
            path = os.path.join(root, rel)
            if os.path.isfile(path):
                files = [path]
            else:
                files = sorted(
                    os.path.join(dp, f)
                    for dp, _dirs, fs in os.walk(path)
                    for f in fs if f.endswith(".py"))
            for f in files:
                h.update(os.path.relpath(f, root).encode())
                with open(f, "rb") as fh:
                    h.update(fh.read())
        _kernel_digest = h.hexdigest()
        return _kernel_digest


def device_signature() -> str:
    """backend:device-kind x count — the topology half of the artifact
    key (a serialized executable is loadable only onto the device set it
    was compiled for)."""
    import jax
    devs = jax.devices()
    return "%s:%s x%d" % (devs[0].platform, devs[0].device_kind, len(devs))


def env_signature() -> Dict[str, str]:
    """The environment an artifact set is valid for; any field drifting
    invalidates the whole index (serve arming refuses it)."""
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_sig": device_signature(),
            "kernel_digest": kernel_digest()}


# ------------------------------------------------------------- signatures


def _leaf_sig(x) -> str:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        sig = "%s[%s]" % (x.dtype.name if hasattr(x.dtype, "name")
                          else str(x.dtype),
                          ",".join(str(d) for d in x.shape))
        # a MULTI-DEVICE array (mesh profile: pmesh shards the cluster,
        # then calls the same seamed Python entries) must never key to an
        # artifact compiled for single-device inputs — the deserialized
        # executable's input-sharding check would reject it.  Tag the
        # mesh placement; single-device arrays and numpy hosts keep the
        # bare signature, so single-chip artifact keys are unchanged.
        sh = getattr(x, "sharding", None)
        if sh is not None:
            try:
                devs = sh.device_set
                if len(devs) > 1:
                    sig += "@%s" % (sh.spec if hasattr(sh, "spec")
                                    else "sharded%d" % len(devs))
            except Exception:  # pragma: no cover - exotic sharding types
                sig += "@sharded"
        return sig
    # python scalars trace as weak rank-0 avals; their VALUE is dynamic
    return "py:%s" % type(x).__name__


def static_sig(statics: Dict[str, Any]) -> str:
    """Stable digest of the static argument values (same convention as
    tools/kubecensus.census._static_sig)."""
    r = repr(sorted((k, repr(v)) for k, v in statics.items()))
    return hashlib.sha256(r.encode()).hexdigest()[:16]


# defaults of each seamed program's keyword parameters, by program name —
# jit resolves an unpassed static kwarg to its function default, so the
# signature must too or `f(x)` and `f(x, mr=None)` would key differently
_defaults_cache: Dict[str, Dict[str, Any]] = {}
_defaults_lock = threading.Lock()


def _kw_defaults(program: str, jitfn) -> Dict[str, Any]:
    with _defaults_lock:
        d = _defaults_cache.get(program)
        if d is None:
            try:
                fn = getattr(jitfn, "__wrapped__", jitfn)
                d = {k: p.default
                     for k, p in inspect.signature(fn).parameters.items()
                     if p.default is not inspect.Parameter.empty}
            except (TypeError, ValueError):  # pragma: no cover - C callables
                d = {}
            _defaults_cache[program] = d
        return d


def call_signature(program: str, jitfn, args: tuple, kwargs: dict,
                   static_argnums: Tuple[int, ...] = (),
                   static_argnames: Tuple[str, ...] = (),
                   ) -> Tuple[str, tuple, dict, dict, str]:
    """(sig_key, dyn_args, dyn_kwargs, norm_kwargs, static_sig) for one
    call.  The key is computable without tracing: program name + static
    digest + the call's pytree structure + per-leaf avals.

    NORMALIZATION — capture and serve must produce byte-identical call
    forms, because a deserialized executable validates its input pytree
    exactly (positional-vs-keyword and a present-but-None kwarg both
    mismatch):

    * static kwargs NOT passed are filled from the function's declared
      defaults (what jit's cache key resolves them to anyway);
    * dynamic kwargs passed as None whose declared default IS None are
      DROPPED from both the signature and the dispatched call — every
      seamed program's optional arrays (host_ok, score_bias, tie_index)
      follow that convention, so `f(x)` and `f(x, host_ok=None)` key and
      call identically.

    dyn_args/dyn_kwargs are the statics-stripped call the compiled
    executable accepts; norm_kwargs is the full normalized keyword dict
    (statics included) the capture side must lower with."""
    import jax

    defaults = _kw_defaults(program, jitfn)
    stat_idx = set(static_argnums)
    statics = {"arg%d" % i: args[i] for i in stat_idx if i < len(args)}
    dyn_args = tuple(a for i, a in enumerate(args) if i not in stat_idx)
    dyn_kwargs = {}
    norm_kwargs = {}
    for k, v in kwargs.items():
        if k in static_argnames:
            statics[k] = v
            norm_kwargs[k] = v
        elif v is None and defaults.get(k, ()) is None:
            continue                       # == omitting it, see docstring
        else:
            dyn_kwargs[k] = v
            norm_kwargs[k] = v
    for k in static_argnames:
        if k not in statics and k in defaults:
            statics[k] = defaults[k]
    ssig = static_sig(statics)
    leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    doc = json.dumps([program, ssig, str(treedef),
                      [_leaf_sig(l) for l in leaves]])
    key = hashlib.sha256(doc.encode()).hexdigest()[:24]
    return key, dyn_args, dyn_kwargs, norm_kwargs, ssig


def pod_bucket_of(args: tuple) -> Optional[int]:
    """The pod-axis bucket of a seam call (cluster is always the first
    argument of the seamed programs) — the unit the flight recorder's
    bucket-hit pruning works in."""
    try:
        return int(args[0].pod_valid.shape[0])
    except Exception:
        return None


# ------------------------------------------------------------------ store


class AotStore:
    """One artifact directory: ``<root>/<program>-<sha16>-<env8>.aotx``
    files plus ``<root>/index.json``.  Serialization format per artifact:
    pickle of {"meta", "payload", "in_tree", "out_tree", "device_ids"}."""

    def __init__(self, root: str):
        self.root = root
        self.index_path = os.path.join(root, INDEX_NAME)

    def _env_key(self, env: Dict[str, str]) -> str:
        doc = json.dumps([env.get("jaxlib"), env.get("backend"),
                          env.get("device_sig")])
        return hashlib.sha256(doc.encode()).hexdigest()[:8]

    def artifact_name(self, program: str, lowering_sha256: str,
                      env: Dict[str, str]) -> str:
        return "%s-%s-%s.aotx" % (program.strip("_"), lowering_sha256[:16],
                                  self._env_key(env))

    def save(self, name: str, meta: Dict[str, Any], payload: bytes,
             in_tree, out_tree, device_ids: List[int]) -> int:
        os.makedirs(self.root, exist_ok=True)
        blob = pickle.dumps({"meta": meta, "payload": payload,
                             "in_tree": in_tree, "out_tree": out_tree,
                             "device_ids": device_ids})
        path = os.path.join(self.root, name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return len(blob)

    def load(self, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.root, name), "rb") as f:
            data = f.read()
        # chaos seam (utils/chaos.py "aot-load"): a truncated blob is
        # what a torn deploy / partial rsync actually produces — the
        # pickle failure below must flow through every caller's
        # degrade-to-trace-path handling, never crash prewarm
        from . import chaos
        if chaos.action("aot-load") is not None:
            data = data[:max(len(data) // 2, 1)]
        return pickle.loads(data)

    def remove(self, name: str) -> None:
        try:
            os.unlink(os.path.join(self.root, name))
        except OSError:
            pass

    # ---- index ----------------------------------------------------------

    def write_index(self, env: Dict[str, str], rows: List[dict],
                    extra_path: Optional[str] = None) -> str:
        doc = {"_comment": INDEX_COMMENT, "env": env,
               "rows": sorted(rows, key=lambda r: (r.get("row") or "",
                                                   r.get("sig_key") or ""))}
        os.makedirs(self.root, exist_ok=True)
        for path in filter(None, (self.index_path, extra_path)):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        return self.index_path

    def read_index(self) -> Optional[dict]:
        try:
            with open(self.index_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


def _load_executable(blob: Dict[str, Any]):
    """deserialize-and-load one artifact onto exactly the devices it was
    compiled for, in compile order — left to its default, the loader
    takes EVERY device of the backend as the executable's device set, and
    a one-device program then rejects its one-shard arguments."""
    import jax
    from jax.experimental import serialize_executable as se
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        blob["payload"], blob["in_tree"], blob["out_tree"],
        execution_devices=[by_id[i] for i in blob["device_ids"]])


# ---------------------------------------------------------------- runtime


class AotRuntime:
    """The serving (or capture) half over one AotStore.

    serve mode: dispatch() resolves the call's signature key against the
    index, deserialize-and-loads the artifact on first use (an
    ``aot-load`` flight span records seconds + hit/miss per bucket), and
    calls the loaded executable with the statics-stripped call.  Any
    miss — unknown signature, unreadable artifact, env drift — falls
    back to the jit (persistent-cache/trace ladder) and is remembered so
    later calls skip the probe.

    capture mode (tools/kubeaot build side): dispatch() compiles the
    exact serving call via ``jit.lower(...).compile()``, serializes it
    into the store, and returns the compiled result so multi-cycle
    prewarm ladders keep chaining."""

    def __init__(self, store: AotStore, mode: str = "serve",
                 env: Optional[Dict[str, str]] = None,
                 family: str = "serving"):
        assert mode in ("serve", "capture")
        self.store = store
        self.mode = mode
        self.family = family
        self.env = env or env_signature()
        self._lock = threading.Lock()
        self._execs: Dict[str, Any] = {}      # kubelint: guarded-by(_lock)
        self._missing: set = set()            # kubelint: guarded-by(_lock)
        self._rows_by_sig: Dict[str, dict] = {}  # kubelint: guarded-by(_lock)
        self._rows: List[dict] = []           # kubelint: guarded-by(_lock)
        self.hits = 0                         # kubelint: guarded-by(_lock)
        self.misses = 0                       # kubelint: guarded-by(_lock)
        self.loads = 0                        # kubelint: guarded-by(_lock)
        self.disabled_reason: Optional[str] = None
        if mode == "serve":
            self._load_index()

    # ---- index / status -------------------------------------------------

    def _load_index(self) -> None:
        doc = self.store.read_index()
        if doc is None:
            self.disabled_reason = "no artifact index at %s" % \
                self.store.index_path
            return
        built = doc.get("env") or {}
        here = self.env
        for field in ("jax", "jaxlib", "backend", "device_sig",
                      "kernel_digest"):
            if built.get(field) != here.get(field):
                self.disabled_reason = (
                    "artifact env mismatch on %s: built %r, serving %r — "
                    "falling back to the persistent-cache/trace path"
                    % (field, built.get(field), here.get(field)))
                LOG.warning(self.disabled_reason)
                return
        with self._lock:
            for row in doc.get("rows", []):
                sig = row.get("sig_key")
                if sig:
                    self._rows_by_sig[sig] = row
                self._rows.append(row)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"mode": self.mode, "hits": self.hits,
                    "misses": self.misses, "loads": self.loads,
                    "indexed": len(self._rows),
                    "disabled": self.disabled_reason}

    def rows(self) -> List[dict]:
        with self._lock:
            return list(self._rows)

    def serving_buckets(self) -> set:
        """Pod-axis buckets the artifact set covers (empty = no pruning
        information; prewarm walks its full ladder)."""
        with self._lock:
            return {r["pod_bucket"] for r in self._rows
                    if r.get("family") == "serving"
                    and r.get("pod_bucket")}

    def allows_bucket(self, bucket: int) -> bool:
        """Ladder pruning: a bucket with no artifact — because the flight
        recorder never saw it and tools/kubeaot --prune dropped it — is
        not worth prewarm's dry-run either."""
        buckets = self.serving_buckets()
        return not buckets or bucket in buckets

    # ---- dispatch -------------------------------------------------------

    def dispatch(self, program: str, jitfn, args: tuple, kwargs: dict,
                 static_argnums: Tuple[int, ...] = (),
                 static_argnames: Tuple[str, ...] = ()):
        if self.disabled_reason is not None:
            return jitfn(*args, **kwargs)
        try:
            key, dyn_args, dyn_kwargs, norm_kwargs, ssig = call_signature(
                program, jitfn, args, kwargs, static_argnums,
                static_argnames)
        except Exception:  # pragma: no cover - malformed seam call
            LOG.warning("aot signature failed for %s", program,
                        exc_info=True)
            return jitfn(*args, **kwargs)
        with self._lock:
            fn = self._execs.get(key)
            missing = key in self._missing
        if fn is None and not missing:
            if self.mode == "capture":
                fn = self._capture(program, key, ssig, jitfn, args,
                                   norm_kwargs)
            else:
                fn = self._load(program, key, args)
        if fn is not None:
            try:
                out = fn(*dyn_args, **dyn_kwargs)
            except Exception:
                # the loaded executable REJECTED the call (input sharding
                # or layout the signature could not see) — the serving
                # contract is "never worse than disarmed": remember the
                # miss and fall back to the jit.  No seamed program
                # donates buffers, so the failed attempt consumed nothing
                # and the retry below is safe.
                LOG.warning("aot executable for %s rejected the call; "
                            "falling back to the trace path", program,
                            exc_info=True)
                with self._lock:
                    self._missing.add(key)
                    self._execs.pop(key, None)
                    self.misses += 1
                return jitfn(*args, **kwargs)
            with self._lock:
                self.hits += 1
            return out
        with self._lock:
            self.misses += 1
        return jitfn(*args, **kwargs)

    # ---- serve side -----------------------------------------------------

    def preload(self, family: Optional[str] = "serving") -> List[dict]:
        """Warm-start fast path (Scheduler.prewarm): deserialize-and-load
        every indexed artifact of ``family`` (None = all) UP FRONT, so
        prewarm's dry-run and the first serving cycle dispatch into
        resident executables — no trace, no lower, no XLA for covered
        call forms.  Returns one report dict per row: {program, variant,
        pod_bucket, seconds, ok}; rows whose artifact is unreadable
        report ok=False and stay on the per-bucket fallback
        (persistent-cache/trace) path."""
        from .trace import flight_span
        report: List[dict] = []
        for row in self.rows():
            if family is not None and row.get("family") != family:
                continue
            key, name = row.get("sig_key"), row.get("artifact")
            if not key or not name:
                continue
            with self._lock:
                if key in self._execs:
                    continue
            t0 = time.time()
            ok = True
            reason = None
            with flight_span("aot-load", program=row.get("program", "?"),
                             bucket=row.get("pod_bucket"), hit=True) as sp:
                try:
                    fn = _load_executable(self.store.load(name))
                except Exception as e:
                    # a corrupt/unreadable artifact (truncated blob, torn
                    # deploy, chaos "aot-load") degrades THIS row to the
                    # per-bucket trace fallback with the reason recorded;
                    # prewarm keeps going — an artifact set is allowed to
                    # be partially rotten without costing availability
                    LOG.warning("aot preload of %s failed; bucket falls "
                                "back to the trace path", name,
                                exc_info=True)
                    ok = False
                    reason = "%s: %s" % (type(e).__name__, e)
                    if sp is not None:
                        sp.args["hit"] = False
                        sp.args["reason"] = reason[:256]
                dt = time.time() - t0
                if sp is not None:
                    sp.args["seconds"] = round(dt, 4)
            if ok:
                with self._lock:
                    self._execs[key] = fn
                    self.loads += 1
            else:
                with self._lock:
                    self._missing.add(key)
            entry = {"program": row.get("program"),
                     "variant": row.get("variant"),
                     "pod_bucket": row.get("pod_bucket"),
                     "seconds": round(dt, 4), "ok": ok}
            if reason is not None:
                entry["reason"] = reason
            report.append(entry)
        return report

    def _load(self, program: str, key: str, args: tuple):
        from .trace import flight_span
        with self._lock:
            row = self._rows_by_sig.get(key)
        bucket = pod_bucket_of(args)
        if row is None or not row.get("artifact"):
            with flight_span("aot-load", program=program, hit=False,
                             bucket=bucket):
                pass
            with self._lock:
                self._missing.add(key)
            return None
        t0 = time.time()
        with flight_span("aot-load", program=program, hit=True,
                         bucket=bucket) as sp:
            try:
                fn = _load_executable(self.store.load(row["artifact"]))
            except Exception:
                LOG.warning("aot artifact %s unreadable; falling back",
                            row["artifact"], exc_info=True)
                if sp is not None:
                    sp.args["hit"] = False
                with self._lock:
                    self._missing.add(key)
                return None
            if sp is not None:
                sp.args["seconds"] = round(time.time() - t0, 4)
        with self._lock:
            self._execs[key] = fn
            self.loads += 1
        return fn

    # ---- capture (build) side ------------------------------------------

    def capture_call(self, program: str, jitfn, args: tuple, kwargs: dict,
                     static_argnums: Tuple[int, ...] = (),
                     static_argnames: Tuple[str, ...] = (),
                     row_name: Optional[str] = None,
                     variant: Optional[str] = None) -> Optional[dict]:
        """Build-side capture WITHOUT execution (tools/kubeaot --build):
        lower + compile + serialize the normalized call form and register
        it, exactly as a capture-mode dispatch would — minus the call.
        ``row_name``/``variant`` override the index row id (the census
        build keys rows by COMPILE_MANIFEST row id so ci_lint.sh can
        compare the two key sets).  Returns the index row, or None when
        the capture failed (the variant stays on the trace path)."""
        try:
            key, _dyn_args, _dyn_kwargs, norm_kwargs, ssig = call_signature(
                program, jitfn, args, kwargs, static_argnums,
                static_argnames)
        except Exception:
            LOG.warning("aot signature failed for %s", program,
                        exc_info=True)
            return None
        with self._lock:
            if key in self._execs:
                return self._rows_by_sig.get(key)
        if self._capture(program, key, ssig, jitfn, args, norm_kwargs,
                         row_name=row_name, variant=variant) is None:
            return None
        with self._lock:
            return self._rows_by_sig.get(key)

    def _capture(self, program: str, key: str, ssig: str, jitfn,
                 args: tuple, norm_kwargs: dict,
                 row_name: Optional[str] = None,
                 variant: Optional[str] = None):
        """norm_kwargs is call_signature's NORMALIZED keyword dict — the
        lower below must see the exact call form serve-side dispatch will
        use, or the executable's input pytree check rejects the call."""
        import hashlib as _h
        try:
            from jax.experimental import serialize_executable as se
            lowered = jitfn.lower(*args, **norm_kwargs)
            sha = _h.sha256(lowered.as_text().encode()).hexdigest()
            compiled = lowered.compile()
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            # build-time round trip: an executable that came back as a
            # PERSISTENT-CACHE HIT serializes to a blob referencing JIT
            # symbols it does not carry (CPU deserialize fails with
            # "Symbols not found"), and a blob that cannot load is a
            # build failure NOW, not a silent trace-path fallback at
            # serve (tools/kubeaot captures under
            # utils/compilation.cache_disabled for this reason)
            _load_executable({"payload": payload, "in_tree": in_tree,
                              "out_tree": out_tree,
                              "device_ids": device_ids})
        except Exception:
            LOG.warning("aot capture failed for %s; serving keeps the "
                        "trace path for this variant", program,
                        exc_info=True)
            with self._lock:
                self._missing.add(key)
            return None
        bucket = pod_bucket_of(args)
        name = self.store.artifact_name(program, sha, self.env)
        row = {"row": row_name or "serving:%s@b%s/%s" % (program,
                                                         bucket or 0, key),
               "family": self.family, "program": program,
               "variant": variant or "b%s" % (bucket or 0),
               "sig_key": key, "static_sig": ssig,
               "lowering_sha256": sha, "artifact": name,
               "pod_bucket": bucket}
        row["bytes"] = self.store.save(name, dict(row), payload, in_tree,
                                       out_tree, device_ids)
        with self._lock:
            self._rows.append(row)
            self._rows_by_sig[key] = row
            self._execs[key] = compiled
            self.loads += 1
        return compiled

    def flush_index(self, extra_path: Optional[str] = None,
                    replace_family: Optional[str] = None) -> str:
        """Write (capture mode) or rewrite the store index, merging with
        any rows already on disk from a previous build.  The merge keys
        on ROW ID (unique per variant; serving rows embed their sig in
        the id), so a re-captured variant REPLACES its previous row — a
        call-form change must not leave the stale signature mapping
        behind, where it would cost a wasted deserialize + rejected call
        at serve.  ``replace_family``: drop ALL existing rows of that
        family first — build_census enumerates the census family
        exhaustively, so rows it did not re-capture are dead variants,
        not partial-build survivors."""
        merged: Dict[str, dict] = {}
        existing = self.store.read_index()
        if existing and (existing.get("env") or {}) == self.env:
            for r in existing.get("rows", []):
                if replace_family and r.get("family") == replace_family:
                    continue
                merged[r.get("row") or r.get("sig_key")] = r
        for r in self.rows():
            merged[r.get("row") or r.get("sig_key")] = r
        return self.store.write_index(self.env, list(merged.values()),
                                      extra_path=extra_path)


# ---------------------------------------------------------------- arming
#
# Same contract as trace.py's recorder arming: _active is read WITHOUT a
# lock on the hot path (rebinding a reference is atomic; a racing reader
# sees old or new), arm/disarm serialize through _active_lock.

_active: Optional[AotRuntime] = None
_active_lock = threading.Lock()
# why the runtime was last disarmed mid-run (the scheduler's
# dispatch-recovery AOT->trace demotion records its reason here so
# /debug and tests can see the ladder rung that fired); None = never
_demotion_reason: Optional[str] = None   # kubelint: guarded-by(_active_lock)


def active_runtime() -> Optional[AotRuntime]:
    return _active


def arm(runtime: AotRuntime) -> AotRuntime:
    global _active
    with _active_lock:
        _active = runtime
    return runtime


def disarm(reason: Optional[str] = None) -> None:
    """Disarm the runtime; a non-None reason marks this as a DEMOTION
    (AOT->trace, the self-healing ladder) rather than a clean teardown."""
    global _active, _demotion_reason
    with _active_lock:
        _active = None
        if reason is not None:
            _demotion_reason = reason


def demotion_reason() -> Optional[str]:
    with _active_lock:
        return _demotion_reason


def reset_demotion() -> None:
    """Clear the demotion latch (operator/test hook) so
    maybe_arm_from_env may arm again."""
    global _demotion_reason
    with _active_lock:
        _demotion_reason = None


def serve_runtime(root: str) -> AotRuntime:
    return AotRuntime(AotStore(root), mode="serve")


def capture_runtime(root: str) -> AotRuntime:
    return AotRuntime(AotStore(root), mode="capture")


def maybe_arm_from_env() -> Optional[AotRuntime]:
    """Scheduler-construction hook: arms the serve runtime iff
    KUBETPU_AOT_DIR names a directory with a readable, env-matching
    index.  Never raises — a bad artifact set must not block serving
    (the trace path still works); it logs and stays disarmed."""
    root = os.environ.get(DIR_ENV, "")
    if not root:
        return None
    if _active is not None:
        return _active
    if demotion_reason() is not None:
        # the self-healing ladder demoted AOT->trace in this process: a
        # later Scheduler construction must not silently re-arm the
        # artifact set that just faulted (explicit arm() still can,
        # reset_demotion() clears the latch)
        LOG.warning("AOT artifacts stay demoted (%s); serving the trace "
                    "path", demotion_reason())
        return None
    try:
        rt = serve_runtime(root)
    except Exception:  # pragma: no cover - index IO is already guarded
        LOG.warning("KUBETPU_AOT_DIR=%s unusable; serving without AOT "
                    "artifacts", root, exc_info=True)
        return None
    if rt.disabled_reason is not None:
        LOG.warning("AOT artifacts disabled: %s", rt.disabled_reason)
        return None
    return arm(rt)


def dispatch(program: str, jitfn, args: tuple, kwargs: dict,
             static_argnums: Tuple[int, ...] = (),
             static_argnames: Tuple[str, ...] = ()):
    """The seam entry: AOT-armed calls resolve against the artifact set,
    disarmed calls go straight to the jit (one attribute read of cost)."""
    rt = _active
    if rt is None:
        return jitfn(*args, **kwargs)
    return rt.dispatch(program, jitfn, args, kwargs,
                       static_argnums=static_argnums,
                       static_argnames=static_argnames)
