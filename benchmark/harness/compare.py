"""The comparisons that decide ``correct``. Each number compared is printed
beside its limit, in every run; the limits live in the configuration's file
with the readings they were set from (``PERF.md`` section 2).
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List


def _verdict(say: Callable[[str], None], name: str, value: float,
             limit: float) -> bool:
    ok = value <= limit
    say(f"compare {name}: {value:.6g} (limit {limit:.6g}) "
        f"{'ok' if ok else 'OVER THE LIMIT'}")
    return ok


def norm_gap(program: Dict[str, float], reference: Dict[str, float]):
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), against the reference's norm of
    that leaf or of the median leaf, whichever is larger: some gradients
    are all but zero. Returns ``(gap, leaf)``."""
    if set(program) != set(reference):
        raise KeyError(
            f"the program and the reference name different parameters: "
            f"{sorted(set(program) ^ set(reference))[:8]}")
    floor = statistics.median(reference.values())
    worst, at = 0.0, ""
    for name, ref in reference.items():
        gap = abs(float(program[name]) - ref) / max(ref, floor, 1e-30)
        if gap > worst:
            worst, at = gap, name
    return worst, at


NO_GRADIENT = 1e-3


def driven_leaves(reference: Dict[str, Any]) -> List[str]:
    """The leaves whose first gradient is more than rounding: at least
    ``NO_GRADIENT`` of the median leaf's. A key bias has no gradient at all
    (the softmax does not see it); Adam divides whatever rounding leaves
    there by its own size, so such a leaf's *change* is full-sized noise in
    any precision and says nothing about the step."""
    grads = reference["grad_norms"]
    floor = NO_GRADIENT * statistics.median(grads.values())
    return [name for name, g in grads.items() if g >= floor]


def train_numbers(program: Dict[str, Any], reference: Dict[str, Any]
                  ) -> Dict[str, float]:
    """The three numbers of a training cell: the worst step's relative loss
    gap, the worst leaf's first-gradient norm gap, the worst leaf's
    parameter-change norm gap (over the leaves that have a gradient)."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(program["loss"], reference["loss"]))
    grad, grad_at = norm_gap(program["grad_norms"], reference["grad_norms"])
    driven = driven_leaves(reference)
    change, change_at = norm_gap(
        {k: program["change_norms"][k] for k in driven},
        {k: reference["change_norms"][k] for k in driven})
    return {"train_loss_rel": loss, "train_grad_norm_gap": grad,
            "train_change_norm_gap": change,
            "_grad_leaf": grad_at, "_change_leaf": change_at,
            "_driven": len(driven)}


def train(program, reference, limits: Dict[str, float], say) -> bool:
    say(f"losses: program {program['loss']}, reference "
        f"{reference['loss']}")
    numbers = train_numbers(program, reference)
    say(f"worst leaves: gradient {numbers['_grad_leaf']}, change "
        f"{numbers['_change_leaf']} (of {numbers['_driven']} leaves with a "
        f"gradient, of {len(reference['grad_norms'])})")
    return all([_verdict(say, k, numbers[k], limits[k])
                for k in ("train_loss_rel", "train_grad_norm_gap",
                          "train_change_norm_gap")])
