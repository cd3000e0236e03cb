"""The comparison that decides ``correct``.

Training numbers (each the worst case over steps or leaves):

- ``loss_gap``: |loss - reference loss| / |reference loss| over the first
  three steps; ``loss1_gap`` the same for the first step alone.
- ``grad_gap``: per dense leaf, the gap between the norms of the program's
  first gradient and the reference's, over the larger of the reference
  leaf's norm and the median leaf's norm.
- ``change_gap``: the same for every leaf's change after three steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: round-off alone moves them.

A configuration's ``limits`` name the numbers it compares, each with its
limit; a run is correct when none passes its limit.
"""
from __future__ import annotations

import statistics

EXCLUDE_BELOW = 1e-3


def _norm_gap(prog: dict, ref: dict, names) -> float:
    names = list(names)
    if not names or any(n not in prog for n in names):
        return float("inf")
    med = statistics.median(ref[n] for n in names)
    gaps = [abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names]
    return _worst(gaps)


def _worst(values) -> float:
    """The largest value; a NaN reading counts as an infinite gap."""
    return max(v if v == v else float("inf") for v in values)


def moved_leaves(ref: dict) -> list[str]:
    """Leaves whose reference first gradient is not nought to rounding."""
    g = ref["grad_norms"]
    med = statistics.median(g.values())
    return sorted(n for n, v in g.items() if v >= EXCLUDE_BELOW * med)


def loss_gaps(prog: dict, ref: dict) -> list[float]:
    """Each step's |loss - reference loss| / |reference loss|."""
    if len(prog["losses"]) != len(ref["losses"]) or not ref["losses"]:
        return [float("inf")]
    return [abs(a - b) / abs(b) for a, b in
            zip(prog["losses"], ref["losses"], strict=True)]


def gaps(prog: dict, ref: dict) -> dict:
    per_step = loss_gaps(prog, ref)
    loss_gap = _worst(per_step)
    dense = [n for n in ref["grad_norms"] if n != "table"]
    grad_gap = _norm_gap(prog["grad_norms"], ref["grad_norms"], dense)
    change_gap = _norm_gap(prog["change_norms"], ref["change_norms"],
                           moved_leaves(ref))
    return {"loss_gap": loss_gap, "loss1_gap": _worst(per_step[:1]),
            "grad_gap": grad_gap, "change_gap": change_gap}


def training(prog: dict, ref: dict, limits: dict) -> dict:
    g = gaps(prog, ref)
    return {k: {"value": g[k], "limit": lim} for k, lim in limits.items()}


def passed(all_checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in all_checks.values())


def lines(all_checks: dict) -> list[str]:
    return [f"check {k} {c['value']!r} limit {c['limit']!r}"
            for k, c in all_checks.items()]
