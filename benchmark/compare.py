"""The comparison that decides ``correct``: numbers the timed path produced
against the plain reference's, each beside a limit of its own
(benchmark/limits/<workload>.json; PERF.md gives the readings each limit was
set from).
"""

from __future__ import annotations

import statistics


def norm_gap(prog: dict[str, float], ref: dict[str, float],
             skip: set[str] = frozenset()) -> tuple[float, str]:
    """Worst leaf's gap between the program's norm and the reference's (not
    the norm of a difference), against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    names = [k for k in ref if k not in skip]
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    median = statistics.median(ref[k] for k in names)
    worst = max(names, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], median))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], median), worst


def still_leaves(ref_grad_norms: dict[str, float]) -> set[str]:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): Adam moves them by round-off alone, so
    their change is not compared."""
    median = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < 1e-3 * median}


def train_numbers(prog: dict, ref: dict) -> dict[str, float]:
    """The training cell's numbers: each checked step's loss, the global norm
    of each checked update's gradient before clipping, the first gradient's
    norms leaf by leaf as the optimizer got them, the parameters' change."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out[f"loss{i}_rel"] = abs(a - b) / abs(b)
    for i, (a, b) in enumerate(zip(prog["grad_norms_global"], ref["grad_norms_global"]), start=1):
        out[f"gnorm{i}_rel"] = abs(a - b) / abs(b)
    out["grad1_norm_gap"], _ = norm_gap(prog["grad1_leaf_norms"], ref["grad1_leaf_norms"])
    skip = still_leaves(ref["grad1_raw_leaf_norms"])
    out["dparam_norm_gap"], _ = norm_gap(prog["delta_leaf_norms"], ref["delta_leaf_norms"], skip)
    return out


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, list[dict]]:
    """Every number the cell's limits file names must be there and within its
    limit. Returns (correct, [{"name", "value", "limit"}...])."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows
