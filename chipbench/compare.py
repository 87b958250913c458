"""The numbers ``correct`` is decided by, and their limits.

Each is a gap between the program and the float32 reference:
- ``loss0``, ``loss1``, ``loss2``: |L - L_ref| / L_ref of each of the
  first three steps' mean loss;
- ``grad``: the first gradient as the optimizer got it, worked out from
  the state after one step, ||theta_0 - theta_1|| / lr, per leaf (one
  layer's tensor of one node): the worst leaf's |norm - norm_ref| over
  the larger of its reference norm and the median leaf's;
- ``grad_p90``: the same gap of the leaf at the 90th percentile, steady
  where the worst leaf is one leaf's own story (PERF.md);
- ``change``: ||theta_3 - theta_0|| per leaf, the same way, over the
  leaves whose float32 reference gradient is at least a thousandth of
  the median leaf's (a smaller one is nought to rounding);
A cell's ``limits/<cell>.json`` names the numbers it compares.
"""

from __future__ import annotations

import numpy as np

TINY_GRAD = 1e-3
GAPS = ("loss0", "loss1", "loss2", "grad", "grad_p90", "change")


def flat(norms: dict) -> np.ndarray:
    return np.concatenate([np.asarray(norms[k], np.float64).ravel()
                           for k in sorted(norms)])


def leaf_gaps(prog: dict, ref: dict, keep=None) -> np.ndarray:
    """Per leaf: |norm - norm_ref| / max(norm_ref, the median leaf's)."""
    p, r = flat(prog), flat(ref)
    if keep is not None:
        p, r = p[keep], r[keep]
    return np.abs(p - r) / np.maximum(r, np.median(r))


def gaps(prog: dict, ref: dict) -> dict:
    """prog, ref: {"losses": [3], "grad": norms, "change": norms};
    ref also has "grad_exact" (the float32 gradient's norms)."""
    out = {f"loss{t}": abs(prog["losses"][t] - ref["losses"][t]) / abs(ref["losses"][t])
           for t in range(3)}
    grad = leaf_gaps(prog["grad"], ref["grad"])
    out["grad"] = float(np.max(grad))
    out["grad_p90"] = float(np.percentile(grad, 90))
    exact = flat(ref["grad_exact"])
    out["change"] = float(np.max(leaf_gaps(prog["change"], ref["change"],
                                           keep=exact >= TINY_GRAD * np.median(exact))))
    return out


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and {name: {"value", "limit"}} for every limited number.

    A number that is not finite, or missing, fails.
    """
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = values.get(name, float("nan"))
        checks[name] = {"value": v, "limit": limit}
        ok &= bool(np.isfinite(v) and v <= limit)
    return ok, checks
