"""What decides ``correct``: the program's first calls against the plain
reference's, from the same seed, data and initial model.

Both sides run the same ``REF_STEPS`` calls of ``rounds_per_call`` rounds.
MRC picks each index by an argmax over noisy importance weights, so one
rounding difference flips an index and two sound runs then differ
element by element; what they share is the size of what moved.  So the
numbers compared are gaps of norms, taken by the worst layer (a "leaf"):

  update_gap  the first call's change of the model
  change_gap  the change after the last call
  est_gap     the change of the clients' estimates after the last call
  loss_gap    the test cross-entropy after each call (worst call), over
              the reference's test loss of the initial model
  acc_gap     the test accuracy at each evaluated round (worst round)
  bits_gap    the bits booked over all calls

A leaf's gap is | |prog_l| - |ref_l| | over the larger of |ref_l| and the
median leaf's |ref|.  Leaves whose first reference update is under a
thousandth of the median leaf's are left out (round-off alone moves them).
Losses are evaluated by the reference's own forward pass at HIGHEST
precision, for the program's model and the reference's alike.  A trained
model's test loss lies near 0, so its gap is taken over the initial
model's loss, which sets the scale of the problem.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

QUIET_LEAF = 1e-3  # of the median leaf's first-update norm


@dataclass
class Step:
    """What one call left behind, on the host."""
    theta: np.ndarray
    theta_hat: np.ndarray
    bits: float
    acc: List[float]
    loss: Optional[float] = None


@dataclass
class Record:
    theta0: np.ndarray
    steps: List[Step] = field(default_factory=list)
    loss0: Optional[float] = None  # test loss of theta0


def _leaf_norms(v: np.ndarray, slices) -> np.ndarray:
    v = np.asarray(v, np.float64)
    return np.array([np.linalg.norm(v[..., s]) for s in slices])


def _worst_gap(p, r, keep) -> float:
    med = float(np.median(r))
    gaps = [abs(a - b) / max(b, med, 1e-30)
            for a, b, k in zip(p, r, keep) if k]
    return float(max(gaps)) if gaps else 0.0


def readings(prog: Record, ref: Record, slices) -> Dict[str, float]:
    t0 = np.asarray(ref.theta0, np.float64)
    p1 = _leaf_norms(prog.steps[0].theta - t0, slices)
    r1 = _leaf_norms(ref.steps[0].theta - t0, slices)
    keep = r1 >= QUIET_LEAF * float(np.median(r1))
    pk, rk = prog.steps[-1], ref.steps[-1]
    out = {
        "update_gap": _worst_gap(p1, r1, keep),
        "change_gap": _worst_gap(_leaf_norms(pk.theta - t0, slices),
                                 _leaf_norms(rk.theta - t0, slices), keep),
        "est_gap": _worst_gap(_leaf_norms(pk.theta_hat - t0, slices),
                              _leaf_norms(rk.theta_hat - t0, slices), keep),
        "loss_gap": max(abs(a.loss - b.loss) / max(abs(ref.loss0), 1e-9)
                        for a, b in zip(prog.steps, ref.steps)),
        "acc_gap": max(abs(x - y) for a, b in zip(prog.steps, ref.steps)
                       for x, y in zip(a.acc, b.acc)),
    }
    pb = sum(s.bits for s in prog.steps)
    rb = sum(s.bits for s in ref.steps)
    out["bits_gap"] = abs(pb - rb) / max(rb, 1.0)
    return {k: float(v) for k, v in out.items()}


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) over the numbers with a limit.
    A number that is not finite fails."""
    rows = [(k, values.get(k, math.nan), float(lim))
            for k, lim in limits.items()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
