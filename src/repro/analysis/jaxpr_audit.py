"""jaxpr pass: trace the real engine calls, prove the kernel is parity-safe.

The pass replays a captured production call of ``vdes.simulate`` /
``vdes.simulate_ensemble`` under ``jax.make_jaxpr`` (static argnames closed
over, array arguments traced) and walks the jaxpr recursively — through
nested ``jit`` bodies and into ``while``/``scan`` subjaxprs — checking:

- **while-fma** — an f32 multiply whose (sole) consumer is an add/sub
  inside the wave-loop body: exactly the shape XLA contracts into an FMA
  while numpy rounds the product first (the PR 5 drift bug). The
  :func:`repro.core.numerics.rounded_product` barrier breaks the pattern,
  so fixed sites audit clean by construction;
- **carry-f64 / carry-weak-type** — the ``lax.while_loop`` carry must be
  fully strongly-typed f32/int: an f64 or weak-typed float in the carry
  means a Python scalar or f64 constant leaked into parity state;
- **f64-const** — f64 constants/literals or ``convert_element_type`` to
  f64 anywhere in the traced kernel;
- **loop-reduce** — order-sensitive float reductions (reduce_sum,
  scatter-add, cumsum, dot) inside the loop body: legal only when the
  numpy mirror provably reduces in the identical order (pragma with the
  proof). Integer reductions are exact in any order and pass;
- **unguarded-div / unguarded-log** — float div (or log/rsqrt) in the loop
  whose denominator (operand) is not guarded by a max/clamp/select:
  batched padding rows mint NaN/inf the numpy mirror never computes.

Findings carry the *user* source line (the innermost ``repro`` frame of the
equation's traceback), so pragmas and baselines attach to engine code, not
to JAX internals.
"""
from __future__ import annotations

import inspect
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.analysis.harness import (CapturedCall, STATIC_ARGNAMES,
                                    capture_calls, smoke_spec)

# order-sensitive float reductions
REDUCE_PRIMS = {"reduce_sum", "cumsum", "scatter-add", "add_any",
                "dot_general"}
# a denominator/operand produced (possibly through shape ops) by one of
# these is considered guarded
GUARD_PRIMS = {"max", "min", "clamp", "select_n"}
# shape/dtype plumbing the pattern matcher looks through
TRANSPARENT_PRIMS = {"broadcast_in_dim", "convert_element_type", "reshape",
                     "squeeze", "expand_dims", "copy", "stop_gradient"}


# ------------------------------------------------------------- re-tracing

def trace_call(call: CapturedCall, kind: str):
    """Re-trace one captured engine call with ``jax.make_jaxpr``. Static
    argnames and ``None`` arguments are closed over; everything else is
    traced, so the jaxpr is the one XLA would compile for this call."""
    import jax

    from repro.core import vdes
    fn = getattr(vdes, kind)
    bound = inspect.signature(fn).bind(*call.args, **call.kwargs)
    named = dict(bound.arguments)
    closed = {k: named.pop(k) for k in list(named)
              if k in STATIC_ARGNAMES or named[k] is None}

    def wrapper(dyn):
        return fn(**dyn, **closed)

    return jax.make_jaxpr(wrapper)(named)


# ---------------------------------------------------------------- walking

def _subjaxprs(value) -> List:
    """Jaxpr objects inside an eqn param value (ClosedJaxpr, Jaxpr, or
    containers thereof)."""
    if hasattr(value, "jaxpr"):                 # ClosedJaxpr
        return [value.jaxpr]
    if hasattr(value, "eqns"):                  # raw Jaxpr
        return [value]
    if isinstance(value, (list, tuple)):
        out = []
        for v in value:
            out.extend(_subjaxprs(v))
        return out
    return []


def _is_float(aval) -> bool:
    import numpy as np
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(dtype, np.floating)


def _is_f64(aval) -> bool:
    import numpy as np
    return getattr(aval, "dtype", None) == np.dtype("float64")


def eqn_site(eqn, root: str) -> Tuple[str, int, str]:
    """``(repo-relative file, line, stripped source line)`` of the innermost
    ``repro`` frame that issued this equation ("" / 0 when unknown)."""
    import linecache
    import os

    tb = getattr(getattr(eqn, "source_info", None), "traceback", None)
    frames = list(getattr(tb, "frames", None) or []) if tb is not None else []
    site: Optional[Tuple[str, int]] = None
    for fr in frames:
        fname = getattr(fr, "file_name", "") or getattr(fr, "filename", "")
        if "/repro/" not in fname.replace(os.sep, "/"):
            continue
        line = int(getattr(fr, "line_num", 0) or getattr(fr, "lineno", 0)
                   or getattr(fr, "start_line", 0) or 0)
        site = (fname, line)
        break       # jax tracebacks are innermost-first: first match wins
    if site is None:
        return "", 0, ""
    fname, line = site
    snippet = linecache.getline(fname, line).strip()
    rel = os.path.relpath(os.path.abspath(fname), os.path.abspath(root))
    return rel.replace(os.sep, "/"), line, snippet


class _JaxprAuditor:
    """One recursive walk, collecting deduplicated findings."""

    def __init__(self, root: str, label: str):
        self.root = root
        self.label = label
        self.findings: List[Finding] = []
        self._seen: set = set()

    def emit(self, rule: str, eqn, message: str) -> None:
        file, line, snippet = eqn_site(eqn, self.root)
        key = (rule, file, line, message if not file else "")
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            rule=rule, file=file, line=line,
            message=f"{message} [traced via {self.label}]",
            snippet=snippet))

    # -- rules ------------------------------------------------------------

    def check_consts(self, closed) -> None:
        import numpy as np
        for const, var in zip(closed.consts, closed.jaxpr.constvars):
            dtype = getattr(const, "dtype", None)
            if dtype is not None and np.dtype(dtype) == np.dtype("float64"):
                self.emit("f64-const", _FakeEqn(),
                          f"f64 constant {getattr(var, 'aval', var)} closed "
                          "over by the traced kernel")

    def check_carry(self, eqn) -> None:
        body = eqn.params.get("body_jaxpr")
        nconsts = eqn.params.get("body_nconsts", 0)
        if body is None:
            return
        for i, aval in enumerate(body.in_avals[nconsts:]):
            if _is_f64(aval):
                self.emit("carry-f64", eqn,
                          f"while-loop carry slot {i} is {aval}: the "
                          "parity contract is f32 op-for-op")
            elif getattr(aval, "weak_type", False) and _is_float(aval):
                self.emit("carry-weak-type", eqn,
                          f"while-loop carry slot {i} is weak-typed "
                          f"{aval}: a bare Python scalar leaked into "
                          "parity state")

    def _producer_through_transparent(self, producers: Dict, var):
        """The eqn producing ``var``, looking through shape plumbing and
        into nested ``jit`` bodies (``jnp.where``/``jnp.maximum`` wrap
        their select/max in a ``jit`` eqn, so the guard lives
        one scope down)."""
        for _ in range(16):
            eqn = producers.get(id(var))
            if eqn is None:
                return None
            if eqn.primitive.name in TRANSPARENT_PRIMS:
                var = eqn.invars[0]
                continue
            if eqn.primitive.name == "jit":
                inner = eqn.params["jaxpr"].jaxpr
                try:
                    idx = [id(v) for v in eqn.outvars].index(id(var))
                except ValueError:
                    return eqn
                ivar = inner.outvars[idx]
                if hasattr(ivar, "val"):
                    return None
                producers = {id(v): e for e in inner.eqns
                             for v in e.outvars}
                var = ivar
                continue
            return eqn
        return None

    def walk(self, jaxpr, in_loop: bool) -> None:
        producers: Dict[int, object] = {}
        n_consumers: Dict[int, int] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if hasattr(v, "aval") and not hasattr(v, "val"):
                    n_consumers[id(v)] = n_consumers.get(id(v), 0) + 1
            for v in eqn.outvars:
                producers[id(v)] = eqn
        for v in jaxpr.outvars:
            n_consumers[id(v)] = n_consumers.get(id(v), 0) + 1

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "while":
                self.check_carry(eqn)
            if name == "pallas_call" and \
                    not _subjaxprs(list(eqn.params.values())):
                # the generic recursion below audits kernel bodies exposed
                # via the eqn params (the `jaxpr` param on this JAX
                # version); if a JAX upgrade hides it, fail loudly instead
                # of silently skipping the kernel
                self.emit("pallas-opaque", eqn,
                          "pallas_call kernel body not found in eqn params "
                          "— the kernel went unaudited")
            if name == "convert_element_type" and \
                    str(eqn.params.get("new_dtype")) == "float64":
                self.emit("f64-const", eqn,
                          "conversion to f64 inside the traced kernel")
            for v in eqn.invars:
                if hasattr(v, "val") and _is_f64(getattr(v, "aval", None)):
                    self.emit("f64-const", eqn,
                              "f64 literal inside the traced kernel")

            if in_loop:
                self._loop_rules(eqn, name, producers, n_consumers)

            loop_like = name in ("while", "scan")
            for sub in _subjaxprs(list(eqn.params.values())):
                self.walk(sub, in_loop or loop_like)

    def _loop_rules(self, eqn, name, producers, n_consumers) -> None:
        if name in ("add", "sub") and _is_float(eqn.outvars[0].aval):
            for v in eqn.invars:
                if not hasattr(v, "aval") or hasattr(v, "val"):
                    continue
                prod = self._producer_through_transparent(producers, v)
                if prod is not None and prod.primitive.name == "mul" \
                        and _is_float(prod.outvars[0].aval) \
                        and n_consumers.get(id(prod.outvars[0]), 0) == 1:
                    op = "+" if name == "add" else "-"
                    self.emit(
                        "while-fma", eqn,
                        f"f32 multiply feeds this `{op}` inside the wave "
                        "loop — XLA may contract it into an FMA; use "
                        "repro.core.numerics.fma_free_madd/msub")
        elif name in REDUCE_PRIMS and _is_float(eqn.outvars[0].aval):
            self.emit("loop-reduce", eqn,
                      f"order-sensitive float {name} inside the wave loop "
                      "— numpy must reduce in the identical order (pragma "
                      "with the proof) or use min/max")
        elif name == "div" and _is_float(eqn.outvars[0].aval):
            den = eqn.invars[1]
            if hasattr(den, "val"):          # literal denominator
                import numpy as np
                if float(np.min(np.abs(den.val))) > 0.0:
                    return
            prod = self._producer_through_transparent(producers, den)
            if prod is not None and prod.primitive.name in GUARD_PRIMS:
                return
            self.emit("unguarded-div", eqn,
                      "float division in the wave loop with an unguarded "
                      "denominator — batched padding rows can mint "
                      "NaN/inf; use repro.core.numerics.guarded_denominator")
        elif name in ("log", "log1p", "rsqrt") and \
                _is_float(eqn.outvars[0].aval):
            prod = self._producer_through_transparent(producers,
                                                      eqn.invars[0])
            if prod is not None and prod.primitive.name in GUARD_PRIMS:
                return
            if hasattr(eqn.invars[0], "val"):
                return
            self.emit("unguarded-log", eqn,
                      f"{name} in the wave loop with an unclamped operand")


class _FakeEqn:
    """Site-less equation stand-in (constvar findings have no traceback)."""
    source_info = None


def audit_closed_jaxpr(closed, root: str, label: str) -> List[Finding]:
    """All jaxpr rules over one traced call."""
    auditor = _JaxprAuditor(root, label)
    auditor.check_consts(closed)
    auditor.walk(closed.jaxpr, in_loop=False)
    return auditor.findings


def audit_carry_only(closed, root: str, label: str) -> List[Finding]:
    """Only the while-carry rules (carry-f64 / carry-weak-type).

    Used for the ``enable_x64`` re-trace: with x64 *off* an f64 constant
    introduced into the carry is silently downcast to f32 — invisible. The
    x64 re-trace lets it keep its declared width so the carry check sees
    it. In-body rules are skipped under x64: jnp scalar helpers
    (clip/where) mint phantom f64 converts there that do not exist in the
    production (x64-off) program."""
    auditor = _JaxprAuditor(root, label)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                auditor.check_carry(eqn)
            for sub in _subjaxprs(list(eqn.params.values())):
                walk(sub)

    walk(closed.jaxpr)
    return auditor.findings


# ------------------------------------------------------------------ entry

def run_jaxpr_audit(root: str) -> List[Finding]:
    """Capture + trace + audit the production engine calls on the smoke
    spec: the single-replica path (``simulate``) and the batched path
    (``simulate_ensemble`` via a 2-point grid)."""
    from repro.core.experiment import Sweep, run_experiment

    findings: List[Finding] = []

    with capture_calls("simulate") as calls:
        run_experiment(smoke_spec(engine="jax"))
    if calls:
        closed = trace_call(calls[0], "simulate")
        findings += audit_closed_jaxpr(closed, root, "vdes.simulate")
        # x64 re-trace: an f64 constant seeded into the carry is downcast
        # (invisible) under the production x64-off config — give it its
        # declared width and re-check the carry
        import jax
        with jax.enable_x64(True):
            closed64 = trace_call(calls[0], "simulate")
        findings += audit_carry_only(closed64, root, "vdes.simulate[x64]")

    mini = Sweep(smoke_spec(engine="jax"),
                 {"trigger:drift_threshold": [0.05, 0.2]})
    with capture_calls("simulate_ensemble") as calls:
        mini.run()
    if calls:
        closed = trace_call(calls[0], "simulate_ensemble")
        findings += audit_closed_jaxpr(closed, root,
                                       "vdes.simulate_ensemble")
        # the Pallas admission fast path is opt-in (admission_sort=
        # "pallas"), so the default traces never contain its kernel:
        # re-trace the same production call with the kernel selected so
        # its body is audited (interpret mode keeps the pallas_call eqn
        # and its kernel jaxpr in the trace)
        call = calls[0]
        call_p = CapturedCall(call.args,
                              {**call.kwargs, "admission_sort": "pallas"})
        closed_p = trace_call(call_p, "simulate_ensemble")
        findings += audit_closed_jaxpr(closed_p, root,
                                       "vdes.simulate_ensemble[pallas]")
    return findings
