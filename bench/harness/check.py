"""The comparison that decides ``correct`` for a training cell.

The program and the reference each train the run's first three steps from
the same seeded weights.  Three numbers are read; a cell compares those
its ``bench/limits/<workload>.json`` gives a limit, set from the readings
kept beside it.  A number that neither the lower-precision control nor a
fault separates from sound runs has no limit there, and its readings are
kept under ``not_compared``:

- ``loss_gap``: the relative gap of the first step's loss (the forward
  pass over the first batch, from the seeded weights).  The later steps'
  losses are left out: AdamW's first updates move each weight by about
  lr x sign(g), so rounding flips the sign of small gradient entries and
  the step-3 loss swings from seed to seed (program against reference:
  0.0002 to 0.0075 on one chip) as much as the lower-precision control's
  does; the change over the three steps is held by ``change_gap``;
- ``grad_gap``: over every leaf (stacked layers apart), the gap between the
  norms of the first step's clipped gradient, as the program's AdamW state
  holds it (m / (1 - b1)) and as the reference computes it;
- ``change_gap``: the same for the parameters' change over the three steps.

A leaf's gap is taken against the reference's norm of that leaf or of the
median leaf, whichever is larger, since some gradients are all but zero.
Leaves whose reference gradient is under a thousandth of the median
leaf's move under AdamW by round-off alone, and are left out of the
change.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Dict

DIR = Path(__file__).resolve().parents[1] / "limits"
NUMBERS = ("loss_gap", "grad_gap", "change_gap")
STILL = 1e-3  # reference gradient, as a share of the median leaf's


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys):
    med = statistics.median(ref[k] for k in keys)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers, with the leaf that sets each of the last two."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("the program's and the reference's leaves differ")
    loss = abs(prog["loss"][0] - ref["loss"][0]) / abs(ref["loss"][0])
    gmed = statistics.median(ref["grad"].values())
    moving = [k for k in ref["change"] if ref["grad"][k] >= STILL * gmed]
    grad, gleaf = _worst(prog["grad"], ref["grad"], list(ref["grad"]))
    change, cleaf = _worst(prog["change"], ref["change"], moving)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "grad_leaf": gleaf, "change_leaf": cleaf,
            "still_leaves": sorted(set(ref["change"]) - set(moving))}


def load_limits(workload: str) -> dict:
    return json.loads((DIR / f"{workload}.json").read_text())


def judge(numbers: dict, limits: dict) -> Dict[str, dict]:
    """{number: {"value", "limit", "ok"}} for each number the limits
    give; a number that is not finite fails."""
    out = {}
    for name in (n for n in NUMBERS if n in limits):
        v, lim = numbers[name], limits[name]["limit"]
        out[name] = {"value": v, "limit": lim,
                     "ok": math.isfinite(v) and v <= lim}
    return out
