"""IR-level program audit: jaxpr walker, static cost model, IRAuditor.

rlint's R001–R007 read Python source; this module reads what actually
ships to the accelerator. :func:`summarize_jaxpr` walks a (closed)
jaxpr — duck-typed, so this module never imports jax and the analysis
package stays importable in milliseconds — collecting the facts the
R100-series rules (:mod:`.irrules`) judge: host-callback primitives,
collectives, f64 creep, dead computation, plus a static FLOPs /
bytes-moved cost model. The compiled executable's HLO text contributes
the facts tracing cannot see: honored input-output aliasing (did XLA
actually take the donation?) and partitioner-inserted collectives.

The auditor piggybacks on :meth:`rl_tpu.compile.CachedProgram._compile`
— the one place every registered program already pays a trace+lower —
so the audit adds **zero dispatch-path cost** and every executable the
ProgramRegistry materializes is checked exactly once per signature.
Findings reuse the :class:`~.findings.Finding` record (the program name
stands in for the file path as ``program:<name>``), so baseline
suppression, fingerprints, and the reason-required triage flow are the
same machinery R001–R007 already use.

Cost model: ``dot_general`` counts ``2·B·M·N·K``, convolutions
``2·|out|·(kernel taps × in-features / groups)``, reductions one flop
per input element, everything else one per output element; ``scan``
bodies multiply by trip count. Bytes are the sum of operand+result
sizes per equation — an un-fused upper bound, which is exactly what a
roofline wants (:func:`roofline` flags transfer-bound programs by
comparing ``flops/peak`` against ``bytes/bandwidth``).
"""

from __future__ import annotations

import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any

from .baseline import Baseline, DEFAULT_BASELINE
from .findings import Finding

__all__ = [
    "IRAuditor",
    "IRCost",
    "IRFacts",
    "ProgramAudit",
    "get_ir_auditor",
    "roofline",
    "summarize_jaxpr",
]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# primitives that re-enter Python from inside the program (R101)
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call",
})
# cross-device primitives (R103); inside shard_map the installed jax
# traces psum / all_gather as their *_invariant forms
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "pmean", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "pbroadcast",
    "reduce_scatter", "psum_scatter",
})
# HLO op names the SPMD partitioner may insert post-trace (R103)
_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)\b"
)
_ALIAS_ENTRY_RE = re.compile(r"(?:may|must)-alias")


def _alias_block(hlo_text: str) -> str:
    """The brace-balanced body of ``input_output_alias={...}`` in an HLO
    module header (nested braces — regex can't scope it reliably)."""
    marker = "input_output_alias={"
    start = hlo_text.find(marker)
    if start < 0:
        return ""
    i = start + len(marker)
    depth = 1
    for j in range(i, min(len(hlo_text), i + 65536)):
        ch = hlo_text[j]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                return hlo_text[i:j]
    return ""

_WIDE_DTYPES = ("float64", "complex128")


# -- cost model ---------------------------------------------------------------

@dataclass
class IRCost:
    """Static per-call cost of one program signature."""

    flops: float = 0.0       # total FLOPs per call
    bytes: float = 0.0       # operand+result bytes summed per equation
    io_bytes: float = 0.0    # program inputs + outputs only
    eqns: int = 0            # equation count (scan bodies counted once)
    by_prim: dict = field(default_factory=dict)  # prim name -> eqn count

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "bytes": self.bytes,
            "io_bytes": self.io_bytes, "eqns": self.eqns,
        }


def _aval(v: Any):
    return getattr(v, "aval", None)


def _nbytes(aval: Any) -> float:
    if aval is None:
        return 0.0
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None:
        return 0.0
    n = 1.0
    for d in shape:
        n *= float(d)
    return n * float(getattr(dtype, "itemsize", 4) or 4)


def _nelems(aval: Any) -> float:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0.0
    n = 1.0
    for d in shape:
        n *= float(d)
    return n


def _dtype_name(aval: Any) -> str:
    return str(getattr(aval, "dtype", ""))


def _inner_jaxprs(params: dict):
    """(closed) jaxprs hiding in an eqn's params: scan/while/cond/pjit
    bodies, shard_map, custom_* — anything with .eqns (or .jaxpr.eqns)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "eqns") or hasattr(getattr(x, "jaxpr", None), "eqns"):
                yield x


def _open(jaxpr: Any):
    """Raw jaxpr for either a ClosedJaxpr or an already-open one."""
    inner = getattr(jaxpr, "jaxpr", None)
    return inner if hasattr(inner, "eqns") else jaxpr


# prims that wrap an opaque device kernel whose body the generic
# per-equation rules can't price (the inner jaxpr runs once PER GRID
# STEP, so recursing into it undercounts; a bare custom_call has no body
# at all) — the kernels.registry formulas price these by call target
_KERNEL_CALL_PRIMS = ("pallas_call", "custom_call", "tpu_custom_call")


def _call_target(params: dict) -> str:
    """Call-target name of a kernel-call eqn: the ``name=`` its
    ``pallas_call`` was given, else the kernel body function's name
    (which a ``functools.partial`` kernel keeps only in the traced
    jaxpr's debug info)."""
    name = params.get("name") or params.get("call_target_name")
    if not name:
        info = getattr(params.get("jaxpr"), "debug_info", None)
        name = getattr(info, "func_name", None)
    return str(name) if name else ""


def _price_kernel_call(target: str, eqn: Any) -> dict | None:
    """Registered-kernel cost for one call eqn, or None (lazy import —
    kernels.registry never imports jax, so this keeps the millisecond
    import budget)."""
    try:
        from ..kernels.registry import price_call

        return price_call(
            target,
            [_aval(v) for v in eqn.invars],
            [_aval(v) for v in eqn.outvars],
        )
    except Exception:
        return None


def _eqn_flops(prim: str, eqn: Any) -> float:
    try:
        if prim == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs = _aval(eqn.invars[0])
            rhs = _aval(eqn.invars[1])
            lsh, rsh = lhs.shape, rhs.shape
            batch = 1.0
            for d in lb:
                batch *= float(lsh[d])
            contract = 1.0
            for d in lc:
                contract *= float(lsh[d])
            m = 1.0
            for i, d in enumerate(lsh):
                if i not in lb and i not in lc:
                    m *= float(d)
            n = 1.0
            for i, d in enumerate(rsh):
                if i not in rb and i not in rc:
                    n *= float(d)
            return 2.0 * batch * m * n * contract
        if prim == "conv_general_dilated":
            out = _aval(eqn.outvars[0])
            rhs = _aval(eqn.invars[1])
            dn = eqn.params.get("dimension_numbers")
            out_feat = float(rhs.shape[dn.rhs_spec[0]]) if dn is not None else 1.0
            taps = _nelems(rhs) / max(out_feat, 1.0)
            groups = float(eqn.params.get("feature_group_count", 1) or 1)
            return 2.0 * _nelems(out) * taps / groups
        if prim.startswith(("reduce_", "arg")) or prim in ("reduce_sum", "cumsum",
                                                           "cumlogsumexp", "cummax"):
            return sum(_nelems(_aval(v)) for v in eqn.invars)
    except Exception:
        pass
    return sum(_nelems(_aval(v)) for v in eqn.outvars)


def summarize_jaxpr(jaxpr: Any, *, dead_bytes_threshold: float = 8192.0) -> "IRFacts":
    """One recursive walk → everything the R100 rules + cost model need.

    ``jaxpr`` is a jax ClosedJaxpr (or raw jaxpr) but is only touched
    through ``.eqns`` / ``.invars`` / ``.outvars`` / ``.aval`` duck
    typing, so callers in tests can also hand in lightweight fakes.
    """
    facts = IRFacts()
    top = _open(jaxpr)
    if top is None or not hasattr(top, "eqns"):
        return facts

    for v in getattr(top, "invars", ()):
        facts.input_dtypes.append(_dtype_name(_aval(v)))

    cost = facts.cost
    try:
        cost.io_bytes = sum(_nbytes(_aval(v)) for v in top.invars) + sum(
            _nbytes(_aval(v)) for v in top.outvars
        )
    except Exception:
        pass

    def walk(jx: Any, mult: float, path: str) -> None:
        jx = _open(jx)
        if jx is None or not hasattr(jx, "eqns"):
            return
        for eqn in jx.eqns:
            prim = getattr(getattr(eqn, "primitive", None), "name", "?")
            cost.eqns += 1
            cost.by_prim[prim] = cost.by_prim.get(prim, 0) + 1
            priced = None
            if prim in _KERNEL_CALL_PRIMS:
                target = _call_target(getattr(eqn, "params", None) or {})
                if target:
                    priced = _price_kernel_call(target, eqn)
                    facts.kernel_sites.append(
                        (target, (priced or {}).get("kernel", ""), path)
                    )
            if priced is not None:
                cost.flops += mult * float(priced.get("flops", 0.0))
                cost.bytes += mult * float(priced.get("bytes", 0.0))
            else:
                cost.flops += mult * _eqn_flops(prim, eqn)
                try:
                    cost.bytes += mult * (
                        sum(_nbytes(_aval(v)) for v in eqn.invars)
                        + sum(_nbytes(_aval(v)) for v in eqn.outvars)
                    )
                except Exception:
                    pass
            if prim in CALLBACK_PRIMS or prim.startswith("debug_"):
                facts.callback_sites.append((prim, path))
            if prim in COLLECTIVE_PRIMS:
                facts.collective_sites.append((prim, path))
            for v in getattr(eqn, "outvars", ()):
                dt = _dtype_name(_aval(v))
                if dt in _WIDE_DTYPES:
                    facts.wide_sites.append((prim, dt, path))
                    break
            params = getattr(eqn, "params", None) or {}
            inner_mult = mult
            if prim == "scan":
                try:
                    inner_mult = mult * float(params.get("length", 1) or 1)
                except Exception:
                    inner_mult = mult
            if priced is None:
                # a priced kernel's formula already covers its body;
                # recursing would double-count (and at 1x, not grid-x)
                for sub in _inner_jaxprs(params):
                    walk(sub, inner_mult, f"{path}/{prim}")

    walk(top, 1.0, "")

    # dead computation (top level only): backward liveness from outputs.
    # Effectful primitives (callbacks, collectives) are always live.
    try:
        # any-consumer map: a dead eqn feeding only other dead eqns is part
        # of a dead *chain* — report just the chain's root, not every link
        consumed = {
            id(iv)
            for eqn in top.eqns
            for iv in eqn.invars
            if not hasattr(iv, "val")
        }
        needed = {id(v) for v in top.outvars}
        for eqn in reversed(top.eqns):
            prim = getattr(getattr(eqn, "primitive", None), "name", "?")
            live = (
                prim in CALLBACK_PRIMS
                or prim in COLLECTIVE_PRIMS
                or bool(getattr(eqn, "effects", None))
                or any(id(v) in needed for v in eqn.outvars)
            )
            if live:
                for v in eqn.invars:
                    if _aval(v) is not None and not hasattr(v, "val"):
                        needed.add(id(v))
            elif not any(id(v) in consumed for v in eqn.outvars):
                dead_b = sum(_nbytes(_aval(v)) for v in eqn.outvars)
                if dead_b >= dead_bytes_threshold:
                    shape = tuple(getattr(_aval(eqn.outvars[0]), "shape", ()))
                    facts.dead_sites.append((prim, dead_b, str(shape)))
        for i, v in enumerate(top.invars):
            if id(v) not in needed and _nbytes(_aval(v)) >= dead_bytes_threshold:
                used = any(
                    any(id(iv) == id(v) for iv in eqn.invars) for eqn in top.eqns
                )
                if not used:
                    facts.dead_inputs.append((i, _nbytes(_aval(v))))
    except Exception:
        pass
    return facts


def honored_alias_count(hlo_text: str) -> int:
    """Entries in the executable's ``input_output_alias`` map — how many
    donated buffers XLA actually reused for outputs."""
    return len(_ALIAS_ENTRY_RE.findall(_alias_block(hlo_text or "")))


def hlo_collectives(hlo_text: str) -> list[str]:
    return sorted(set(_HLO_COLLECTIVE_RE.findall(hlo_text or "")))


def roofline(cost: IRCost, peak_flops: float, peak_bytes_per_s: float = 0.0) -> dict:
    """Predicted step time / MFU from the static cost model.

    ``predicted_s = max(flops/peak, bytes/bw)``; a program is
    *transfer-bound* when the byte term dominates — on such a program
    measured MFU can never reach peak no matter how good the kernels
    are, which is the actionable signal for the bench `ir_audit`
    section."""
    out: dict[str, Any] = {
        "flops": cost.flops, "bytes": cost.bytes,
        "intensity": cost.flops / cost.bytes if cost.bytes else 0.0,
    }
    if peak_flops <= 0.0:
        return out
    compute_s = cost.flops / peak_flops
    transfer_s = cost.bytes / peak_bytes_per_s if peak_bytes_per_s > 0.0 else 0.0
    predicted_s = max(compute_s, transfer_s)
    out["predicted_s"] = predicted_s
    out["bound"] = "transfer" if transfer_s > compute_s else "compute"
    out["transfer_bound"] = transfer_s > compute_s
    out["predicted_mfu"] = (compute_s / predicted_s) if predicted_s > 0.0 else 0.0
    return out


# -- facts + audit records ----------------------------------------------------

@dataclass
class IRFacts:
    """What one walk of a lowered program established (rule input)."""

    callback_sites: list = field(default_factory=list)   # (prim, path)
    collective_sites: list = field(default_factory=list)  # (prim, path)
    wide_sites: list = field(default_factory=list)       # (prim, dtype, path)
    dead_sites: list = field(default_factory=list)       # (prim, bytes, shape)
    dead_inputs: list = field(default_factory=list)      # (argpos, bytes)
    input_dtypes: list = field(default_factory=list)
    kernel_sites: list = field(default_factory=list)     # (target, kernel, path)
    cost: IRCost = field(default_factory=IRCost)


@dataclass
class ProgramAudit:
    """One audited (program, signature) with its verdicts."""

    name: str
    fingerprint: str = ""
    facts: IRFacts | None = None
    findings: list = field(default_factory=list)      # all Findings
    unsuppressed: list = field(default_factory=list)
    donated_declared: int = 0
    donated_honored: int = 0
    hlo_collectives: list = field(default_factory=list)

    @property
    def cost(self) -> IRCost | None:
        return self.facts.cost if self.facts is not None else None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "fingerprint": self.fingerprint,
            "findings": [f.to_dict() for f in self.findings],
            "unsuppressed": len(self.unsuppressed),
            "donated": {"declared": self.donated_declared,
                        "honored": self.donated_honored},
            "cost": self.cost.to_dict() if self.cost else None,
        }


class IRAuditor:
    """Collects per-program audits across a process (or a test fixture).

    One process-default instance (:func:`get_ir_auditor`) receives every
    audit the default ProgramRegistry triggers — the tier-1 gate and the
    ``/metrics`` counter read it. Tests that *deliberately* compile
    poisoned programs pass their own instance to
    ``ProgramRegistry(auditor=...)`` so the gate stays clean.
    """

    def __init__(self, baseline_path: str | None = None,
                 dead_bytes_threshold: float = 8192.0):
        self.baseline_path = (
            baseline_path
            if baseline_path is not None
            else os.path.join(_REPO, DEFAULT_BASELINE)
        )
        self.dead_bytes_threshold = dead_bytes_threshold
        self._lock = threading.Lock()
        self._baseline: Baseline | None = None
        self.reports: dict[tuple, ProgramAudit] = {}  # (name, sig_key) -> audit

    def _load_baseline(self) -> Baseline:
        with self._lock:
            if self._baseline is None:
                try:
                    self._baseline = Baseline.load(self.baseline_path)
                except Exception:
                    self._baseline = Baseline(path=self.baseline_path)
            return self._baseline

    def audit(
        self,
        *,
        name: str,
        fingerprint: str = "",
        jaxpr: Any = None,
        compiled_text: str = "",
        donated_leaves: int = 0,
        donation_declared: bool = False,
        contract: dict | None = None,
        sig_key: Any = None,
    ) -> ProgramAudit:
        from .irrules import run_ir_rules

        facts = (
            summarize_jaxpr(jaxpr, dead_bytes_threshold=self.dead_bytes_threshold)
            if jaxpr is not None
            else None
        )
        honored = honored_alias_count(compiled_text)
        hlo_colls = hlo_collectives(compiled_text)
        report = ProgramAudit(
            name=name,
            fingerprint=fingerprint,
            facts=facts,
            donated_declared=donated_leaves,
            donated_honored=honored,
            hlo_collectives=hlo_colls,
        )
        report.findings = run_ir_rules(
            name=name,
            facts=facts,
            donated_leaves=donated_leaves,
            donation_declared=donation_declared,
            honored_aliases=honored,
            hlo_collectives=hlo_colls,
            contract=contract or {},
        )
        unsup, _sup, _stale = self._load_baseline().split(report.findings)
        report.unsuppressed = unsup
        with self._lock:
            self.reports[(name, sig_key)] = report
        return report

    # -- introspection ---------------------------------------------------

    def _snapshot(self) -> list[ProgramAudit]:
        with self._lock:
            return list(self.reports.values())

    def findings(self) -> list:
        return [f for r in self._snapshot() for f in r.findings]

    def unsuppressed(self) -> list:
        return [f for r in self._snapshot() for f in r.unsuppressed]

    def counts_by_rule(self) -> dict:
        from .irrules import IR_RULES

        out = {rid: 0 for rid in IR_RULES}
        for f in self.findings():
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def programs_audited(self) -> int:
        return len(self.reports)

    def report_for(self, name: str) -> ProgramAudit | None:
        """Most recent audit for a program name (any signature)."""
        best = None
        for (n, _), r in sorted(self.reports.items(), key=lambda kv: str(kv[0])):
            if n == name:
                best = r
        return best


_default_auditor: IRAuditor | None = None
_default_lock = threading.Lock()


def get_ir_auditor(create: bool = True) -> IRAuditor | None:
    """Process-default auditor (created on first use)."""
    global _default_auditor
    with _default_lock:
        if _default_auditor is None and create:
            _default_auditor = IRAuditor()
        return _default_auditor


def set_ir_auditor(aud: IRAuditor | None) -> IRAuditor | None:
    global _default_auditor
    with _default_lock:
        prev = _default_auditor
        _default_auditor = aud
        return prev
