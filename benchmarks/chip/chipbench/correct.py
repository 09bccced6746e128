"""The numbers that decide ``correct``, and the verdict against the cell's
limits (``limits/<workload>.json``).

Training (the first ``check_rounds`` rounds that set-up drives through the
window's own ``Session.round()``):

* ``loss_gap``: the largest relative gap of a round's mean loss;
* ``grad_gap``: Adam's first moment after round 1 (the gradients as the
  optimizer got them, exponentially weighted over the round's local steps),
  per leaf: |program norm - reference norm| / max(reference norm, median
  leaf's reference norm), the worst leaf;
* ``change_gap``: the same for the posterior's change over the rounds.

Leaves (mean or rho of one parameter array, over all agents) whose
reference first moment is under a thousandth of the median leaf's are left
out of both: they move by round-off alone.

A quarantined gossip cell also compares ``quarantine_miscount``: how far
the program's count of rejected contributions over those rounds is from
the reference's (exact).
"""
from __future__ import annotations

import numpy as np

EXCLUDE_BELOW = 1e-3  # of the median leaf's reference first moment


def _leaf_gap(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med)
               for k in keep)


def train_numbers(prog: dict, ref: dict) -> dict:
    med = float(np.median(list(ref["grad"].values())))
    keep = [k for k, v in ref["grad"].items() if v >= EXCLUDE_BELOW * med]
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    out = {"loss_gap": float(loss),
           "grad_gap": float(_leaf_gap(prog["grad"], ref["grad"], keep)),
           "change_gap": float(_leaf_gap(prog["change"], ref["change"], keep))}
    if "quarantined" in ref:
        out["quarantine_miscount"] = float(
            abs(prog.get("quarantined", -1) - ref["quarantined"]))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(every number within its limit, {name: {"value", "limit"}})."""
    checks = {k: {"value": v, "limit": float(limits[k])}
              for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
