"""The comparison that decides ``correct``: the program against the reference.

Both sides give, for the first steps of one seed: each step's loss, the
step-0 gradient entropy (where the cell measures it), each leaf's norm of
the first gradient as Adam takes it, and each leaf's norm of the change of
parameters over the steps. A number compared is a gap:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap`` and ``change_gap``: the worst leaf's gap between the two
  sides' norms, over the reference's norm of that leaf or of the median
  leaf, whichever is larger. ``change_gap`` leaves out the leaves whose
  reference gradient is under a thousandth of the median leaf's, which
  move under Adam by round-off alone;
* ``entropy_gap``: the gap of the step-0 entropy, in nats.
"""
from __future__ import annotations

import math
import statistics

GRAD_FLOOR = 1e-3


def leaf_gaps(prog: dict[str, float], ref: dict[str, float], keys=None
              ) -> list[tuple[float, str]]:
    """Each leaf's gap and path, largest first."""
    keys = list(ref) if keys is None else list(keys)
    med = statistics.median(ref[k] for k in keys)
    return sorted(((abs(prog[k] - ref[k]) / max(ref[k], med), k)
                   for k in keys), reverse=True)


def moved(ref: dict) -> list[str]:
    """The leaves whose reference gradient is not nought to rounding."""
    med = statistics.median(ref["grad"].values())
    return [k for k, g in ref["grad"].items() if g >= GRAD_FLOOR * med]


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """The gaps between the program's readings and the reference's."""
    out = {"loss_gap": max(abs(p - r) / abs(r)
                           for p, r in zip(prog["loss"], ref["loss"])),
           "grad_gap": leaf_gaps(prog["grad"], ref["grad"])[0][0],
           "change_gap": leaf_gaps(prog["change"], ref["change"],
                                   moved(ref))[0][0]}
    if prog.get("entropy") is not None:
        out["entropy_gap"] = abs(prog["entropy"] - ref["entropy"])
    return out


def judge(values: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, dict[str, dict[str, float]]]:
    """``correct`` and each number beside its limit. A number that is
    missing or not finite fails."""
    table = {name: {"value": values.get(name, math.nan), "limit": limit}
             for name, limit in limits.items()}
    ok = all(math.isfinite(row["value"]) and row["value"] <= row["limit"]
             for row in table.values())
    return ok, table
