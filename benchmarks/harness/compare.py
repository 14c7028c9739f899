"""The arithmetic of ``correct``: gaps between the program's readings and
the reference's, each a plain number held against a limit of its own."""

from __future__ import annotations

import math
import statistics


def leaf_gaps(program: dict, reference: dict, skip=()) -> list[float]:
    """|program - reference| of every leaf's norm, each measured against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves are all but zero)."""
    names = [n for n in reference if n not in skip]
    if set(program) != set(reference):
        return [math.inf]
    med = statistics.median(reference[n] for n in names)
    return [abs(program[n] - reference[n]) / max(reference[n], med, 1e-30) for n in names]


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> float:
    return max(leaf_gaps(program, reference, skip))


def median_leaf_gap(program: dict, reference: dict, skip=()) -> float:
    return statistics.median(leaf_gaps(program, reference, skip))


def negligible_leaves(ref_grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is under ``share`` of the median
    leaf's: Adam moves them by round-off alone, so their change is not
    compared."""
    med = statistics.median(ref_grad_norms.values())
    return {n for n, g in ref_grad_norms.items() if g < share * med}


def finite(x: float, cap: float = 1e30) -> float:
    """A number JSON can carry: NaN and infinities read as ``cap``, which
    no limit admits."""
    x = float(x)
    return x if math.isfinite(x) else cap


def judge(values: dict, limits: dict, log) -> dict:
    """The numbers a traffic file gives a limit are compared, each beside
    its limit; the others are printed as observed and judge nothing."""
    missing = set(limits) - set(values)
    if missing:
        raise KeyError(f"limits name numbers the driver does not read: {sorted(missing)}")
    for name, v in values.items():
        if name not in limits:
            log(f"observed {name}: {v:.6g} (not compared)")
    return {name: {"value": finite(values[name]), "limit": float(lim)} for name, lim in limits.items()}
