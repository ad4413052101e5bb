"""The comparison that decides `correct`: the program's first three steps
against the plain reference's, on the same weights and batches.

Up to four numbers, each a worst case, each against a limit of the
cell's own (workloads/<cell>.json); a number the cell gives no limit is
reported and not compared:

  loss    the largest relative gap between the program's and the
          reference's loss over the three steps;
  grad    the first gradient as AdamW took it (after clipping), leaf by
          leaf (a stacked leaf split into its layers): the largest gap
          between the two norms, over the larger of the reference's norm of
          that leaf and of the median leaf;
  update  the same for the norm of each leaf's change after three steps,
          over the leaves whose first reference gradient is at least
          MOVED_FLOOR of the median leaf's (a leaf with none moves under
          AdamW by round-off alone);
  residual  on the int8 wire with error feedback, the same for each
          leaf's residual after three steps: the rounding error that the
          wire carries into the next step.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss", "grad", "update", "residual")
MOVED_FLOOR = 1e-3


def _gap(a: float, b: float, scale: float) -> float:
    gap = abs(a - b) / scale
    return gap if math.isfinite(gap) else math.inf


def leaf_gaps(prog: dict, ref: dict, keys) -> dict:
    """Each leaf's gap between the two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    keys = sorted(keys)
    median = statistics.median(ref[k] for k in keys)
    return {k: _gap(prog[k], ref[k], max(ref[k], median, 1e-30))
            for k in keys}


def numbers(prog: dict, ref: dict) -> dict:
    """prog, ref: {"losses": [3], "grad_norms": {leaf: norm},
    "change_norms": {leaf: norm}, "residual_norms": {leaf: norm} or
    None}."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("the program's leaves are not the reference's")
    return {k: max(v.values()) for k, v in gaps(prog, ref).items()}


def gaps(prog: dict, ref: dict) -> dict:
    """The gaps that `numbers` takes the worst of: per step for the loss,
    per leaf for the norms; `residual` only where the reference carries
    one."""
    g_ref = ref["grad_norms"]
    floor = MOVED_FLOOR * statistics.median(g_ref.values())
    moved = [k for k, v in g_ref.items() if v >= floor]
    out = {
        "loss": {f"step{i + 1}": _gap(a, b, abs(b)) for i, (a, b) in
                 enumerate(zip(prog["losses"], ref["losses"], strict=True))},
        "grad": leaf_gaps(prog["grad_norms"], g_ref, g_ref),
        "update": leaf_gaps(prog["change_norms"], ref["change_norms"],
                            moved),
    }
    r_ref = ref.get("residual_norms")
    if r_ref is not None:
        r_prog = prog.get("residual_norms") or {}
        out["residual"] = leaf_gaps({k: r_prog.get(k, 0.0) for k in r_ref},
                                    r_ref, r_ref)
    return out


def verdict(values: dict, limits: dict) -> bool:
    """Every number the cell compares (those it gives a limit) within it."""
    return all(values[k] <= limits[k] for k in limits)
