"""Whether what the timed path produced is correct: the numbers compared.

Training (the first segments of the first run, which set-up drove through the
window's own call, against ``reference.train_segments`` from the same seed):

  objective_gap     max over the segments of |f_prog − f_ref| / |f_ref|, f the
                    primal objective of the segment's consensus
  first_update_gap  after the first segment, the worst leaf's gap of norms of
                    the change from W = 0: |‖Δp‖ − ‖Δr‖| / max(‖Δr‖, median ‖Δr‖)
  change_gap        the same after the last checked segment
  state_dist        after the last checked segment, the worst leaf's
                    ‖p − r‖ / max(‖r‖, median ‖r‖)

A leaf is one node's row of W or of W_sum. Leaves whose reference change is
under a thousandth of the median leaf's are left out (none are at these
settings: every node's row moves).

Serving (every request due in the window, against ``reference.scores`` on the
plane that was live when its drain ran):

  score_err         max over answered requests of |s_prog − s_ref| / (‖w_x‖·‖x‖),
                    w_x the weights the query's nonzeros touch
  unanswered        requests with no score a minute after the window closed

Every number is held to its limit in ``limits/<cell>.json``; a number that is
not finite fails.
"""
from __future__ import annotations

import math

import numpy as np

import reference


def _leaves(W, W_sum):
    return list(np.asarray(W)) + list(np.asarray(W_sum))


def worst_norm_gap(prog, ref) -> float:
    p = np.array([np.linalg.norm(x) for x in prog])
    r = np.array([np.linalg.norm(x) for x in ref])
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    return float(np.max(np.abs(p - r)[keep] / np.maximum(r[keep], med)))


def worst_dist(prog, ref) -> float:
    r = np.array([np.linalg.norm(x) for x in ref])
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    d = np.array([np.linalg.norm(np.asarray(a) - np.asarray(b)) for a, b in zip(prog, ref)])
    return float(np.max(d[keep] / np.maximum(r[keep], med)))


def training_numbers(checked, ref) -> dict:
    """``checked`` and ``ref``: one (W, W_sum, objective) per segment."""
    obj = max(abs(p[2] - r[2]) / abs(r[2]) for p, r in zip(checked, ref))
    first = worst_norm_gap(_leaves(*checked[0][:2]), _leaves(*ref[0][:2]))
    last_p, last_r = _leaves(*checked[-1][:2]), _leaves(*ref[-1][:2])
    return {"objective_gap": obj, "first_update_gap": first,
            "change_gap": worst_norm_gap(last_p, last_r),
            "state_dist": worst_dist(last_p, last_r)}


def reference_segments(run, state, dtype=None, fault=None):
    import jax.numpy as jnp

    cfg = run.config
    d = state["data"]
    data = (d["cols"], d["vals"]) if cfg["dataset"]["layout"] == "ell" else d["X"]
    return reference.train_segments(
        data, d["y"], d["counts"], run.train_seed, cfg["gadget"], cfg["dataset"]["d"],
        int(cfg["gadget"]["check_every"]), len(run.checked),
        dtype=dtype or jnp.float32, fault=fault)


def serving_numbers(run, cols, vals, control: bool = False) -> dict:
    """``control`` puts the reference computed in bfloat16 in the program's place."""
    answered = np.isfinite(run.score)
    idx = np.nonzero(answered)[0]
    err = 0.0
    for v in np.unique(run.version[idx]):
        sel = idx[run.version[idx] == v]
        w = run.planes[int(v)]
        q = run.queries[sel]
        want = reference.scores(w, cols[q], vals[q])
        got = reference.scores(w, cols[q], vals[q], "bfloat16") if control else run.score[sel]
        touched = np.asarray(w, np.float64)[cols[q]] * (vals[q] != 0)
        scale = np.linalg.norm(touched, axis=1) * np.linalg.norm(
            np.asarray(vals[q], np.float64), axis=1)
        err = max(err, float(np.max(np.abs(got - want) / np.maximum(scale, 1e-30))))
    return {"score_err": err, "unanswered": int(len(run.due) - len(idx))}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {value, limit}})."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise LookupError(f"no limit for {name!r} in the cell's limits file")
        lim = limits[name]
        good = value is not None and math.isfinite(value) and value <= lim
        ok &= bool(good)
        out[name] = {"value": value, "limit": lim}
    return ok, out
