"""Kernel registry: feature detection + cost pricing for the Pallas tier.

The serving and replay hot paths each have two implementations: a Pallas
kernel (gather-free paged-attention decode, fused top-k/temperature
sampling, int8-KV dequant-in-kernel, fused sum-tree update) and a
stock-XLA fallback. This module is the ONE place that decides which one
a trace gets, and the one place the rest of the framework asks about it:

- :func:`register_kernel` declares a kernel: the backends whose Mosaic
  lowering supports it, the jaxpr call-target substrings its
  ``pallas_call`` shows up under, a static FLOPs/bytes formula, and its
  exactness tier (``bit-exact`` / ``distribution-exact`` /
  ``accuracy-gated``). The four tier kernels self-register below.
- :func:`selection` resolves a kernel to ``"native"`` (real Mosaic
  lowering), ``"interpret"`` (Pallas interpret mode — how tier-1 proves
  parity on CPU), or ``None`` (the stock-XLA path).
  ``RL_TPU_NO_KERNELS`` force-disables (``1`` = all, or a comma list of
  kernel names); ``RL_TPU_KERNELS_INTERPRET`` opts interpret mode in on
  any backend but ``"tpu"``, where it is an error.
- :func:`price_call` is the IR cost model's hook
  (:func:`rl_tpu.analysis.ir.summarize_jaxpr`): a ``pallas_call`` counts
  0 FLOPs / 0 bytes under the generic per-equation rules, which would
  silently corrupt the roofline ``predicted_mfu`` the moment a kernel
  lands — so the auditor looks the call target up here and charges the
  registered formula instead.
- :func:`expected_active` backs rlint rule R106 (hot-path-on-fallback):
  a registered serving/PER program that declares a
  ``kernel_hot_path`` contract but lowered without the kernel's call
  target, while this registry says the kernel should be active, is an
  unsuppressed finding.

No jax import at module scope — :mod:`rl_tpu.analysis` imports this
lazily and must stay importable in milliseconds.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

__all__ = [
    "KernelSpec",
    "expected_active",
    "fingerprint_selection_drift",
    "kernel_targets",
    "kernels_fingerprint",
    "price_call",
    "refuse_interpret_on_tpu",
    "register_kernel",
    "registered_kernels",
    "selection",
    "status",
    "wire_kernel_obs",
]

ENV_NO_KERNELS = "RL_TPU_NO_KERNELS"
ENV_INTERPRET = "RL_TPU_KERNELS_INTERPRET"

# exactness tiers (docs/kernels.md): how kernel-vs-fallback parity is
# gated in tier-1
BIT_EXACT = "bit-exact"
DISTRIBUTION_EXACT = "distribution-exact"
ACCURACY_GATED = "accuracy-gated"


@dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: identity, support matrix, cost formula."""

    name: str
    # jaxpr call-target substrings this kernel's pallas_call lowers under
    # (the ``name=`` the call passes: the kernel body function's name)
    targets: tuple = ()
    # backends whose native Mosaic lowering supports the kernel
    backends: tuple = ("tpu",)
    # static cost model: (in_avals, out_avals) -> {"flops": f, "bytes": b}
    # (avals duck-typed: .shape / .dtype.itemsize, same as analysis.ir)
    cost: Callable[[list, list], dict] | None = None
    exactness: str = BIT_EXACT
    doc: str = ""


_LOCK = threading.Lock()
_KERNELS: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    with _LOCK:
        _KERNELS[spec.name] = spec
    return spec


def registered_kernels() -> dict[str, KernelSpec]:
    with _LOCK:
        return dict(_KERNELS)


def _disabled(name: str) -> bool:
    raw = os.environ.get(ENV_NO_KERNELS, "").strip()
    if not raw or raw == "0":
        return False
    if raw in ("1", "all", "true"):
        return True
    return name in {p.strip() for p in raw.split(",")}


def _backend() -> str:
    import jax

    return jax.default_backend()


def refuse_interpret_on_tpu(switch: str, backend: str | None = None) -> None:
    """Raise if ``switch`` (an interpret-mode override that is set) meets
    backend ``"tpu"``: there the Pallas interpreter would run where Mosaic
    is available and be reported as the kernel tier."""
    if (backend if backend is not None else _backend()) == "tpu":
        raise RuntimeError(
            f"{switch} is set on backend 'tpu': the Pallas interpreter is a "
            "CPU test mode, not a way to run on the chip — unset it"
        )


def selection(name: str, backend: str | None = None) -> str | None:
    """``"native"`` | ``"interpret"`` | ``None`` (stock-XLA path).

    Interpret mode is how tier-1 proves parity on a host without the
    chip (``RL_TPU_KERNELS_INTERPRET=1``). On backend ``"tpu"`` it is
    never a mode: the override set there would run the Pallas
    interpreter where Mosaic is available and report it as the kernel
    tier, so it raises.
    """
    spec = _KERNELS.get(name)
    if spec is None or _disabled(name):
        return None
    b = backend if backend is not None else _backend()
    if os.environ.get(ENV_INTERPRET, "") not in ("", "0"):
        refuse_interpret_on_tpu(ENV_INTERPRET, b)
        return "interpret"
    if b in spec.backends:
        return "native"
    return None


def expected_active(name: str, backend: str | None = None) -> bool:
    """Should programs on this backend be lowering with this kernel?
    (R106: True + no matching call target in the jaxpr = a hot path
    silently regressed to the stock-XLA fallback.)"""
    return selection(name, backend) is not None


def kernel_targets(name: str) -> tuple:
    spec = _KERNELS.get(name)
    return spec.targets if spec is not None else ()


def kernels_fingerprint() -> str:
    """Selection state folded into program fingerprints: an executable
    compiled with a kernel baked in must never be store-loaded by a
    process running the fallback (and vice versa)."""
    sel = {n: selection(n) for n in sorted(_KERNELS)}
    return "kernels:" + ",".join(f"{n}={m or 'off'}" for n, m in sel.items())


def fingerprint_selection_drift(fingerprint: str) -> list[str]:
    """Kernel names whose selection embedded in ``fingerprint`` (via
    :func:`kernels_fingerprint` at registration time) differs from the
    CURRENT selection — the runtime complement of R106: a non-empty
    result means the executable was built under a different kernel
    regime than this process now runs (a mid-run ``RL_TPU_NO_KERNELS``
    flip, or a store-loaded stale executable). [] when the fingerprint
    embeds no kernel state or it matches."""
    i = fingerprint.find("kernels:")
    if i < 0:
        return []
    # the fragment rides inside a repr() tuple: name=mode pairs, comma
    # separated, terminated by the first char outside the pair alphabet
    frag = fingerprint[i + len("kernels:"):]
    embedded: dict[str, str] = {}
    for pair in frag.split(","):
        name, sep, mode = pair.partition("=")
        name = name.strip()
        mode = "".join(c for c in mode if c.isalnum() or c == "_")
        if not sep or not name.replace("_", "").isalnum() or not mode:
            break  # ran past the fragment into the surrounding repr
        embedded[name] = mode
        if not pair.rstrip().endswith(mode):  # terminator inside this pair
            break
    drifted = []
    for name, mode in embedded.items():
        if name not in _KERNELS:
            continue
        if (selection(name) or "off") != mode:
            drifted.append(name)
    return sorted(drifted)


def status() -> dict:
    """Per-kernel feature-detection matrix for /metrics and the bench
    artifact: mode, backend, exactness tier."""
    b = _backend()
    out = {}
    for name, spec in registered_kernels().items():
        out[name] = {
            "mode": selection(name, b) or "fallback",
            "backend": b,
            "native_backends": list(spec.backends),
            "exactness": spec.exactness,
        }
    return out


# -- IR cost pricing ----------------------------------------------------------

def _nelems(aval: Any) -> float:
    n = 1.0
    for d in getattr(aval, "shape", ()) or ():
        n *= float(d)
    return n


def _nbytes(aval: Any) -> float:
    dt = getattr(aval, "dtype", None)
    return _nelems(aval) * float(getattr(dt, "itemsize", 4) or 4)


def price_call(target: str, in_avals: list, out_avals: list) -> dict | None:
    """Static cost of one kernel custom-call, looked up by call target.

    Returns ``{"flops": f, "bytes": b, "kernel": name}`` when a
    registered kernel's target matches, else ``None`` (the auditor falls
    back to its generic per-equation rules). Formula failures degrade to
    operand+result bytes with zero flops rather than raising — a cost
    model must never break a compile.
    """
    if not target:
        return None
    for name, spec in registered_kernels().items():
        if not any(t in target for t in spec.targets):
            continue
        base = {
            "flops": 0.0,
            "bytes": sum(_nbytes(a) for a in in_avals)
            + sum(_nbytes(a) for a in out_avals),
            "kernel": name,
        }
        if spec.cost is not None:
            try:
                got = spec.cost(list(in_avals), list(out_avals))
                base.update({k: float(v) for k, v in got.items()})
            except Exception:
                pass
        return base
    return None


# -- the four tier kernels ----------------------------------------------------
#
# Cost formulas receive the pallas_call's operand/result avals in call
# order. They are upper bounds in the same spirit as the generic model
# (un-fused bytes), which is what the roofline wants.


def _cost_paged_decode(in_avals: list, out_avals: list) -> dict:
    # operands: table [S, max_blocks], lens [S], then rank-1 scalars (the
    # f32 kernel's chunk list, the int8 variant's scales [N*Hk] x2),
    # q [S*Hk, Gp, D] (the int8 variant: [S*H, 8, D]), k_flat/v_flat
    # [N*Hk, block, D] once per operand — q and the pools are the only
    # rank-3 operands, q first
    table = in_avals[0]
    rank3 = [a for a in in_avals if len(getattr(a, "shape", ()) or ()) == 3]
    q, k_flat = rank3[0], rank3[1]
    # query rows as the q operand counts them: S*H for MHA and for the
    # int8 variant; the f32 kernel packs a GQA group's heads into one
    # row block, so under GQA this prices its FLOPs a group short
    rows = float(q.shape[0])
    D = float(q.shape[-1])
    block = float(k_flat.shape[1])
    max_blocks = float(table.shape[1])
    L = max_blocks * block
    # per attendable position per head: QK dot (2D) + PV dot (2D)
    flops = 4.0 * rows * L * D
    kv_item = float(getattr(getattr(k_flat, "dtype", None), "itemsize", 4) or 4)
    # the bound is a full table: every (row, table entry) moves one K and
    # one V block of one kv head. The int8 variant's grid does fetch per
    # (slot, q-head, entry); the f32 kernel fetches an entry's Hk heads
    # whole, once a slot, and only the entries under the slot's length
    bytes_ = rows * max_blocks * block * D * kv_item * 2.0
    bytes_ += _nbytes(q) + sum(_nbytes(a) for a in out_avals)
    return {"flops": flops, "bytes": bytes_}


def _cost_sampling(in_avals: list, out_avals: list) -> dict:
    # operands: x [S, V] (temperature-scaled logits), gumbel [S, V], ...
    x = in_avals[0]
    n = _nelems(x)
    # softmax (max, sub, exp, sum, log, sub) + noise add + argmax ≈ 8/elem
    return {
        "flops": 8.0 * n,
        "bytes": sum(_nbytes(a) for a in in_avals)
        + sum(_nbytes(a) for a in out_avals),
    }


def _cost_sumtree(in_avals: list, out_avals: list) -> dict:
    # operands: idx [B], delta [B], priorities [P], esum [NB]
    b = _nelems(in_avals[0]) if in_avals else 0.0
    return {
        "flops": 4.0 * b,  # two read-add-writes per update
        "bytes": sum(_nbytes(a) for a in in_avals)
        + sum(_nbytes(a) for a in out_avals),
    }


register_kernel(KernelSpec(
    name="paged_attention",
    targets=("_paged_decode_kernel",),
    cost=_cost_paged_decode,
    exactness=DISTRIBUTION_EXACT,  # online vs full softmax: toleranced
    doc="gather-free paged-KV decode read over PR 11 block tables",
))
register_kernel(KernelSpec(
    name="sampling",
    targets=("_fused_sample_kernel",),
    cost=_cost_sampling,
    exactness=BIT_EXACT,
    doc="fused top-k/temperature sampling for sample_tokens",
))
register_kernel(KernelSpec(
    name="kv_int8",
    # NOT "_paged_decode_kernel_int8": price_call matches by substring and
    # the f32 kernel's target would shadow it
    targets=("_paged_decode_int8_kernel",),
    cost=_cost_paged_decode,
    exactness=ACCURACY_GATED,
    doc="int8 KV pool with per-(block, kv-head) scales, dequant-in-kernel",
))
register_kernel(KernelSpec(
    name="sumtree",
    targets=("_sumtree_update_kernel",),
    cost=_cost_sumtree,
    exactness=BIT_EXACT,
    doc="fused PER sum-tree leaf write + block-sum propagation",
))


# -- observability ------------------------------------------------------------

_OBS_WIRED = False


def wire_kernel_obs() -> None:
    """Publish ``rl_tpu_kernel_active{kernel,backend}`` gauges at scrape
    time (selection is env-driven, so it is re-resolved per scrape).
    Idempotent; failures never propagate (obs is optional)."""
    global _OBS_WIRED
    with _LOCK:
        if _OBS_WIRED:
            return
        _OBS_WIRED = True
    try:
        from ..obs import get_registry

        obs = get_registry()
        g = obs.gauge(
            "rl_tpu_kernel_active",
            "Pallas kernel tier selection (1 = kernel lowering active, "
            "0 = stock-XLA fallback); RL_TPU_NO_KERNELS opts out",
            labels=("kernel", "backend"),
        )

        def collect():
            for name, st in status().items():
                g.set(
                    0.0 if st["mode"] == "fallback" else 1.0,
                    {"kernel": name, "backend": st["backend"] or "?"},
                )

        obs.register_collector(collect)
    except Exception:
        pass
