"""The comparison that decides ``correct`` for a training cell.

Five numbers are compared between what the program's timed step produced
over the first ``reference.STEPS`` steps and what the plain reference
produced from the same weights and batches:

- ``loss1``, ``loss2``, ``loss3``: each step's loss, as the gap
  ``|program - reference| / |reference|``;
- ``grad1``: the norm of the first step's gradient, as the optimizer got it,
  per leaf, by the worst leaf;
- ``dparam3``: the norm of each leaf's change over the steps, by the worst
  leaf, leaving out leaves whose reference gradient is under
  ``NEGLIGIBLE`` times the median leaf's (they move under Adam by round-off
  alone);
- ``grad1_median``, ``dparam3_median``: the same two, by the median leaf's
  gap instead of the worst. Where a leaf's gradient is a sum with heavy
  cancellation (a GroupNorm parameter of an early block), the rounding of
  one bfloat16 pass moves its norm by a tenth or more in sound runs and in
  the control alike; the median leaf's gap still tells them apart.

A leaf's gap is the gap between the program's norm and the reference's,
over the reference's norm of that leaf or of the median leaf, whichever is
larger. Each number has its own limit, in ``bench/limits/<cell>.json``;
a number whose limit is ``null`` is printed and not compared.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

NUMBERS = ("loss1", "loss2", "loss3", "grad1", "dparam3", "grad1_median",
           "dparam3_median")
NEGLIGIBLE = 1e-3


def load_limits(path: str | Path) -> dict:
    limits = json.loads(Path(path).read_text())["limits"]
    missing = [n for n in NUMBERS if n not in limits]
    if missing:
        raise KeyError(f"{path}: no limit for {missing}")
    return limits


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-leaf gaps between two vectors of leaf norms."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not prog.size:
        raise ValueError(f"leaf norms of shape {prog.shape} and {ref.shape}")
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def worst_leaf(prog: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-leaf gap between two vectors of leaf norms."""
    return float(np.max(leaf_gaps(prog, ref)))


def median_leaf(prog: np.ndarray, ref: np.ndarray) -> float:
    """Median per-leaf gap between two vectors of leaf norms."""
    return float(np.median(leaf_gaps(prog, ref)))


def gaps(prog, ref) -> dict:
    """The compared numbers for program readings ``prog`` against reference
    readings ``ref`` (both ``reference.Readings``)."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog.losses, ref.losses, strict=True)):
        out[f"loss{i + 1}"] = (abs(lp - lr) / abs(lr) if math.isfinite(lp)
                               else math.inf)
    out["grad1"] = worst_leaf(prog.grad1, ref.grad1)
    g = np.asarray(ref.grad1, np.float64)
    keep = g >= NEGLIGIBLE * np.median(g)
    dp, dr = np.asarray(prog.dparam)[keep], np.asarray(ref.dparam)[keep]
    out["dparam3"] = worst_leaf(dp, dr)
    out["grad1_median"] = median_leaf(prog.grad1, ref.grad1)
    out["dparam3_median"] = median_leaf(dp, dr)
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(whether every compared number is within its limit, the checks as
    ``{name: {"value": v, "limit": l}}``)."""
    checks, ok = {}, True
    for name in NUMBERS:
        v, lim = values[name], limits[name]
        checks[name] = {"value": v, "limit": lim}
        if lim is not None and not v <= lim:
            ok = False
    return ok, checks


def lines(checks: dict) -> list:
    """One line per number: name, value, limit."""
    return [f"check {n} {c['value']!r} limit {c['limit']!r}"
            for n, c in checks.items()]
