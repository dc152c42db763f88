"""The comparison that decides ``correct``.

Set-up drives the program through its first rounds, and the harness keeps
the global model and the auxiliary heads after each of the first three.
Once the window has closed and the program's state is freed, the plain
reference (``reference.py``) runs the same three rounds from the same
weights, on the same clients' batches, with the tiers that the program's
scheduler chose, and these numbers are read:

- ``logit_gap``: over the three rounds, the largest ||f_p - f_r|| /
  ||f_r - f_0||, with f the logits of the evaluation batch (computed by the
  reference at HIGHEST) under the program's model (p), the reference's (r)
  and the initial one (0): how far the program's training lies from the
  reference's, against how far training moved the model;
- ``loss_gap``: over the three rounds, the largest |L_p - L_r| / L_r of the
  evaluation loss;
- ``first_change_gap`` and ``change_gap``: after the first round and after
  the third, the worst leaf's gap between the norms of its change,
  | ||x_p - x_0|| - ||x_r - x_0|| |, against the reference's norm of that
  leaf's change or the median leaf's, whichever is larger. Leaves whose
  reference change is under a thousandth of the median leaf's are left
  out: they are the heads of tiers that no client trained;
- ``median_change_gap``: after the third round, the median over the leaves
  of that gap. The worst leaf is nearly always a GroupNorm scale or bias of
  16 to 256 elements, whose gap swings from seed to seed; the median does
  not.

A state left unchanged reads 1 on ``logit_gap`` and on the change gaps.
A number is compared only where its cell's limits file gives it a limit.
"""
from __future__ import annotations

import math

import jax
import numpy as np

import reference

MOVED = 1e-3      # a leaf moved by less than this share of the median is left out


def flat(params, aux) -> dict:
    """Leaves of the global model and the heads, by path, as float64."""
    out = {}
    for tree, prefix in ((params, "params"), ({str(k): v for k, v in aux.items()}, "aux")):
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            out[prefix + jax.tree_util.keystr(path)] = np.asarray(x, np.float64)
    return out


def leaf_gaps(init: dict, prog: dict, ref: dict) -> dict:
    """Each moved leaf's gap between the norms of its change."""
    dr = {k: np.linalg.norm(ref[k] - init[k]) for k in init}
    dp = {k: np.linalg.norm(prog[k] - init[k]) for k in init}
    med = float(np.median(list(dr.values())))
    return {k: abs(dp[k] - dr[k]) / max(dr[k], med) for k in init
            if dr[k] >= MOVED * med}


def numbers(arch, init, prog_states, ref_states, eval_batch) -> dict:
    """The numbers above for the program's ``prog_states`` against the
    reference's ``ref_states`` (lists of (params, aux) after each round),
    both started from ``init``."""
    images, labels = eval_batch["images"], eval_batch["labels"]
    f0, _ = reference.evaluate(arch, init[0], images, labels)
    logit, loss = [], []
    for p, r in zip(prog_states, ref_states):
        fp, lp = reference.evaluate(arch, p[0], images, labels)
        fr, lr = reference.evaluate(arch, r[0], images, labels)
        logit.append(np.linalg.norm(fp - fr) / np.linalg.norm(fr - f0))
        loss.append(abs(lp - lr) / lr)
    fi = flat(*init)
    first = leaf_gaps(fi, flat(*prog_states[0]), flat(*ref_states[0]))
    last = leaf_gaps(fi, flat(*prog_states[-1]), flat(*ref_states[-1]))
    top = lambda g: max(g, key=lambda k: (math.isnan(g[k]), g[k]))
    return {"logit_gap": worst(logit), "loss_gap": worst(loss),
            "first_change_gap": first[top(first)], "change_gap": last[top(last)],
            "median_change_gap": float(np.median(list(last.values()))),
            "_leaves": {"first_change_gap": top(first), "change_gap": top(last)}}


def worst(values) -> float:
    """The largest value, where a NaN counts as larger than any."""
    return max(values, key=lambda v: (math.isnan(v), v))


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) for the numbers with a limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
