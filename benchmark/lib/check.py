"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

The limits are data, in the configuration's file under ``correct``, with
the readings they were set from (PERF.md section 2 has the table): above
the largest that sound runs of the program gave over a dozen seeds and
below the smallest that the lower-precision control gave.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple

from .harness import say

Row = Tuple[str, float, float]  # name, value, limit


def leaf_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger: some leaves'
    gradients are all but zero."""
    floor = statistics.median(reference)
    return max(abs(p - r) / max(r, floor)
               for p, r in zip(program, reference))


def train_rows(program: Dict[str, List[float]],
               reference: Dict[str, List[float]],
               limits: Dict[str, float]) -> List[Row]:
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"], reference["losses"]))
    return [
        ("loss_rel_gap", loss_gap, limits["loss_rel_gap"]),
        ("first_grad_leaf_gap",
         leaf_gap(program["first_grad_norms"], reference["first_grad_norms"]),
         limits["first_grad_leaf_gap"]),
        ("param_change_leaf_gap",
         leaf_gap(program["delta_norms"], reference["delta_norms"]),
         limits["param_change_leaf_gap"]),
    ]


def judge(rows: Sequence[Row], what: str) -> bool:
    """Print every number beside its limit; all have to hold. A value that
    is not finite fails whatever the limit."""
    ok = True
    for name, value, limit in rows:
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        say(f"correct[{what}] {name} = {value:.6g}  limit {limit:.6g}  "
            f"{'ok' if good else 'FAILED'}")
    return ok
