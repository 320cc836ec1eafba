"""The comparison that decides ``correct``.

Both sides start from the same weights and data.  The program's side is what
the timed path itself left behind in set-up's first rounds, which go through
the window's own call on the window's own object: each round's
``train_loss`` and the server's parameters after round 1 and after the last
compared round.  The reference's side is ``reference.run_rounds``.

Numbers, each with a limit of its own (``cells/<workload>.json``):

- ``loss_r<k>``: ``|prog - ref| / |ref|`` of round k's benign mean loss.
- ``agg1_worst_leaf``: the first aggregate as the server's optimizer gets it,
  worked out from the state after one round (``params_1 - params_0``; the
  server step is plain SGD, so the step is the aggregate times the server's
  rate).  Per leaf, the gap between the program's norm and the reference's
  (not the norm of their difference), against the reference's norm of that
  leaf or of the median leaf, whichever is larger; the worst leaf counts.
- ``change_worst_leaf``: the same on ``params_R - params_0``.  Leaves whose
  first aggregate is nought to rounding in the reference (under a thousandth
  of the median leaf's) are left out of it, by that rule and not by name.

- ``agg1_diff`` and ``change_diff``: the norm of the difference between the
  two sides' steps over the norm of the reference's, all parameters taken
  as one vector.  The gaps of norms above are blind to an error that is
  spread like noise (it moves a norm only in second order, and the median
  over a thousand clients averages it): rounding every operand to fp8 moved
  them by no more than bf16 does.  The difference's norm is first order in
  such an error, and the whole vector's norm has no all-but-zero leaf to
  blow up on.

- ``change_energy``: ``change_diff`` squared, the share of the step's energy
  that is error.  An error spread like noise adds to a floor both sides
  share in quadrature, not in norm: in the deeper cell the fp8 control reads
  1.8 times the sound runs' ``change_diff`` and 3.4 times their energy, and
  the sound runs' own readings lie within 4% of each other from seed to seed.

A state the step left unchanged reads 1 on the leaf numbers, the diffs and
the energy.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree, path=()):
    """``(name, leaf)`` in sorted order, the leaf as the tree holds it."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], path + (k,))
    else:
        yield "/".join(path), tree


def _together(*trees):
    """``(name, leaf of each tree as float64)``, one leaf at a time: no
    tree-sized float64 copy is ever held."""
    for named in zip(*map(_leaves, trees), strict=True):
        names = {k for k, _ in named}
        if len(names) != 1:
            raise ValueError(f"the trees differ: {sorted(names)}")
        yield named[0][0], [np.asarray(v, np.float64) for _, v in named]


def leaf_norms(after, before) -> dict:
    return {k: float(np.linalg.norm(a - b))
            for k, (a, b) in _together(after, before)}


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def diff_share(prog_after, ref_after, before_prog, before_ref) -> float:
    """``|step_prog - step_ref| / |step_ref|`` over all parameters."""
    num = den = 0.0
    for _, (p1, r1, p0, r0) in _together(prog_after, ref_after, before_prog,
                                         before_ref):
        step = r1 - r0
        num += float(np.sum(np.square((p1 - p0) - step)))
        den += float(np.sum(np.square(step)))
    gap = np.sqrt(num / max(den, 1e-300))
    return float(gap) if np.isfinite(gap) else float("inf")


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``{"losses": [R], "params0", "params": [R]}``
    (``prog`` may hold only rounds 1 and R in ``params``: keys 0 and -1)."""
    out = {}
    rounds = len(ref["losses"])
    for k in range(rounds):
        p, r = float(prog["losses"][k]), float(ref["losses"][k])
        gap = abs(p - r) / abs(r)
        out[f"loss_r{k + 1}"] = gap if np.isfinite(gap) else float("inf")
    a_prog = leaf_norms(prog["params"][0], prog["params0"])
    a_ref = leaf_norms(ref["params"][0], ref["params0"])
    out["agg1_worst_leaf"], where_a = worst_leaf_gap(a_prog, a_ref)
    med = float(np.median(list(a_ref.values())))
    still = [k for k, v in a_ref.items() if v < 1e-3 * med]
    c_prog = leaf_norms(prog["params"][-1], prog["params0"])
    c_ref = leaf_norms(ref["params"][-1], ref["params0"])
    out["change_worst_leaf"], where_c = worst_leaf_gap(c_prog, c_ref, still)
    out["agg1_diff"] = diff_share(prog["params"][0], ref["params"][0],
                                  prog["params0"], ref["params0"])
    out["change_diff"] = diff_share(prog["params"][-1], ref["params"][-1],
                                    prog["params0"], ref["params0"])
    out["change_energy"] = out["change_diff"] ** 2
    out["_where"] = {"agg1_worst_leaf": where_a,
                     "change_worst_leaf": where_c, "left_out": still}
    return out


def decide(nums: dict, limits: dict) -> tuple:
    """``(correct, [[name, value, limit], ...])``: every number that has a
    limit is held to it; a number the cell's file gives no limit is shown
    with ``null`` and not held (``PERF.md`` says which and why)."""
    report, ok = [], True
    for name, value in nums.items():
        if name.startswith("_"):
            continue
        limit = limits.get(name)
        report.append([name, value, limit])
        if limit is not None and not value <= limit:
            ok = False
    for name in limits:
        if name not in nums:
            report.append([name, None, limits[name]])
            ok = False
    return ok, report
