"""jaxpr-level semantic rules: the checks AST lint cannot express.

Every rule runs on the TRACED program (closed jaxpr / lowered module), not
on source text, so it sees through call indirection, Python-level
branching on statics, and closure capture:

  census/donation-unconsumed   a donate_argnums buffer the lowering could
                               not alias into any output (shape/dtype
                               mismatch or unused input) — today only the
                               runtime warnings hook sees this, and only
                               when KUBETPU_SANITIZE=1 is armed
  census/f64-promotion         a float64 value appears in the traced
                               graph when the declared inputs are 32-bit
                               — detected by re-tracing under x64 so
                               latent np.float64 promotions that the
                               default config silently truncates surface
                               statically
  census/host-callback         io_callback / pure_callback /
                               debug_callback reachable from a kernel
                               root: a host round-trip inside the device
                               program
  census/rank-promotion        the trace fails under
                               jax_numpy_rank_promotion="raise" — an
                               implicit broadcast in the traced graph
  census/constant-capture      a closed-over array above the size
                               threshold baked into the program as a
                               literal (shipped with EVERY executable and
                               re-hashed on every compile-cache probe)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

# closed-over constants at or above this many bytes are findings
CONST_CAPTURE_THRESHOLD = 256 * 1024

_CALLBACK_PRIMITIVES = frozenset({
    "io_callback", "pure_callback", "debug_callback", "host_callback_call",
    "outside_call",
})


@dataclasses.dataclass
class Finding:
    rule: str
    program: str
    message: str
    suppressed: bool = False
    reason: str = ""

    def to_json(self) -> dict:
        return {"rule": self.rule, "program": self.program,
                "message": self.message, "suppressed": self.suppressed,
                "reason": self.reason}

    def __str__(self) -> str:
        tag = " (suppressed: %s)" % self.reason if self.suppressed else ""
        return "%s: [%s] %s%s" % (self.program, self.rule, self.message, tag)


def _walk_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (pjit bodies, scan/while/cond branches, custom calls)."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)


def _sub_jaxprs(v):
    from jax.extend import core
    if isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _iter_avals(jaxpr):
    for j in _walk_jaxprs(jaxpr):
        for v in j.invars + j.outvars + j.constvars:
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
        for eqn in j.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield aval


def check_host_callbacks(program: str, closed_jaxpr) -> List[Finding]:
    out = []
    for j in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in _CALLBACK_PRIMITIVES:
                out.append(Finding(
                    "census/host-callback", program,
                    "primitive %r reachable from the kernel root — a host "
                    "round-trip inside the device program"
                    % eqn.primitive.name))
    return out


def check_constant_capture(program: str, closed_jaxpr,
                           threshold: int = CONST_CAPTURE_THRESHOLD
                           ) -> List[Finding]:
    import numpy as np
    out = []
    consts = list(closed_jaxpr.consts)
    for j in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            for v in eqn.params.values():
                if hasattr(v, "consts"):
                    consts.extend(v.consts)
    for c in consts:
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None:
            try:
                nbytes = np.asarray(c).nbytes
            except Exception:
                continue
        if nbytes >= threshold:
            out.append(Finding(
                "census/constant-capture", program,
                "closed-over array of %d bytes (shape %s) baked into the "
                "program as a literal — pass it as an argument instead"
                % (nbytes, getattr(c, "shape", "?"))))
    return out


def check_f64(program: str, jaxpr_fn, args) -> List[Finding]:
    """Re-trace under x64 with the SAME declared (32-bit) input avals;
    any float64 aval in the graph is a latent promotion the default
    config silently truncates."""
    import numpy as np
    import jax
    out = []
    try:
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(jaxpr_fn)(*args)
    except Exception as e:  # a trace that only works in x32 is itself news
        return [Finding("census/f64-promotion", program,
                        "trace failed under x64: %r" % (e,))]
    hits = set()
    for aval in _iter_avals(closed.jaxpr):
        dt = getattr(aval, "dtype", None)
        if (dt is not None and dt == np.float64
                and not getattr(aval, "weak_type", False)):
            # weak f64 = a Python float literal, canonicalized to f32
            # under the serving config with identical value — only
            # COMMITTED (non-weak) f64 marks a real promotion
            hits.add(str(aval.str_short()) if hasattr(aval, "str_short")
                     else str(aval))
    for h in sorted(hits)[:4]:
        out.append(Finding(
            "census/f64-promotion", program,
            "float64 value %s appears in the traced graph under x64 with "
            "32-bit inputs — a latent promotion (np.float64 operand or "
            "f64 literal) the x64-disabled default silently truncates"
            % h))
    return out


def check_rank_promotion(program: str, jaxpr_fn, args) -> List[Finding]:
    """Trace with jax_numpy_rank_promotion='raise'; a failing trace means
    an implicit broadcast inside the program."""
    import jax
    prev = jax.config.jax_numpy_rank_promotion
    try:
        jax.config.update("jax_numpy_rank_promotion", "raise")
        jax.eval_shape(jaxpr_fn, *args)
    except Exception as e:
        msg = str(e).splitlines()[0][:200]
        return [Finding(
            "census/rank-promotion", program,
            "trace fails under rank_promotion=raise: %s" % msg)]
    finally:
        jax.config.update("jax_numpy_rank_promotion", prev)
    return []


def check_donation(program: str, lowered, donate_argnums,
                   n_donated_leaves: Optional[int] = None) -> List[Finding]:
    """The lowering-level half of donation verification: jax annotates
    every HONORED donation as an input/output alias
    (``tf.aliasing_output``) in the lowered module; donated buffers that
    carry no alias could not be consumed (shape/dtype mismatch or unused
    input) and will be silently copied at runtime.  ``n_donated_leaves``:
    flattened leaf count of the donated args, for the partial case."""
    if not donate_argnums:
        return []
    text = lowered.as_text()
    aliased = text.count("tf.aliasing_output")
    if aliased == 0:
        return [Finding(
            "census/donation-unconsumed", program,
            "donate_argnums=%s but the lowered module aliases no input "
            "into any output — XLA cannot reuse the donated buffers"
            % (tuple(donate_argnums),))]
    if n_donated_leaves is not None and aliased < n_donated_leaves:
        return [Finding(
            "census/donation-unconsumed", program,
            "only %d of %d donated buffers alias an output — the rest "
            "are silently copied (shape/dtype mismatch between donated "
            "input and every output)" % (aliased, n_donated_leaves))]
    return []
