"""The one general load generator: a serving mix's schedule from its parameters.

A serving mix (the ``"serve"`` part of ``traffic/<name>.json``) is data:

  "rate_qps": r          mean offered queries per second
  "arrivals": "poisson"  arrivals spread uniformly over the window
              "on_off"   arrivals only in the "on" part of each period, with
  "burst": {"period_s": p, "on_share": f}
                         every period starting "on" for f·p seconds; the rate
                         in a burst is r / f
  "query_nnz": {...}     optional: the query lengths (see ``datagen``); by
                         default every query has the configuration's
                         nonzeros per row
  "rows_per_batch", "min_k"
                         the serving bucket ladder (``bucket_ladder``)

Every seed sends the same amount of work: the count is rate × seconds, and
the seed draws only where in the window (inside the "on" stretches) each
arrival falls and which test row it sends. A Poisson process conditioned on
its count is uniform over the time it runs, so this is the Poisson process
of that rate, with the count held fixed.
"""
from __future__ import annotations

import numpy as np


def on_stretches(spec: dict, seconds: float) -> np.ndarray:
    """(start, end) of every stretch of the window in which arrivals fall."""
    kind = spec["arrivals"]
    if kind == "poisson":
        return np.array([[0.0, seconds]])
    if kind == "on_off":
        period, share = float(spec["burst"]["period_s"]), float(spec["burst"]["on_share"])
        if not (period > 0 and 0 < share <= 1):
            raise ValueError(f"bad burst {spec['burst']!r}")
        starts = np.arange(0.0, seconds, period)
        return np.stack([starts, np.minimum(starts + share * period, seconds)], axis=1)
    raise LookupError(f"unknown arrival process {kind!r} (known: poisson, on_off)")


def schedule(spec: dict, seed: int, seconds: float, n_queries: int):
    """Due times (s from the window's start, ascending) and the test row
    each arrival sends."""
    rng = np.random.default_rng(seed)
    n = int(round(spec["rate_qps"] * seconds))
    spans = on_stretches(spec, seconds)
    lengths = spans[:, 1] - spans[:, 0]
    ends = np.cumsum(lengths)
    u = np.sort(rng.random(n)) * ends[-1]
    k = np.minimum(np.searchsorted(ends, u, side="right"), len(spans) - 1)
    due = spans[k, 0] + u - (ends[k] - lengths[k])
    return due, rng.integers(0, n_queries, n)
