"""Promote a fresh benchmark JSON into a per-runner-class baseline.

Two modes:

* **Promote** (default): copy ``--fresh out.json`` to
  ``benchmarks/baselines/<stem>.<slug>.json`` where ``<slug>`` is derived
  from the JSON's own embedded ``runner`` fingerprint
  (``check_regression.fingerprint_slug``). This is the committed artifact
  that arms the wall-clock gate for the recording machine's class — the
  scripted version of step 3 in benchmarks/README.md's bootstrap recipe.

* **Bootstrap** (``--hosted``): synthesize a *provisional* baseline for the
  pinned CI runner class (``ubuntu-24.04`` hosted: linux/x86_64/3.11/cpu,
  Pallas interpret on, 4 cores) from a run recorded elsewhere. The runner
  fingerprint is rewritten to the hosted class and every wall-clock leaf is
  inflated by ``--headroom`` (default 3.0x) so the first real hosted runs
  cannot hard-fail on machine-class speed differences; structural leaves are
  copied verbatim (they are machine-independent by construction). The
  baseline notes its provenance under a ``bootstrap`` key (strings only —
  invisible to the leaf diff). Replace it with a real green bench-smoke
  artifact (plain promote mode) once one exists; until then the gate is
  armed with conservative numbers rather than not at all.

Usage:
    python benchmarks/promote_baseline.py --fresh fault_bench.json --stem BENCH_faults
    python benchmarks/promote_baseline.py --fresh BENCH_faults.json --stem BENCH_faults --hosted
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from benchmarks.check_regression import (WALLCLOCK_LEAVES, WALLCLOCK_PARENTS,
                                         fingerprint_slug)

# The fingerprint of CI's pinned runner class (.github/workflows/ci.yml:
# runs-on: ubuntu-24.04, python 3.11, JAX_PLATFORMS=cpu so the kernels
# interpret, 4-core hosted image).
HOSTED_FINGERPRINT = {
    "os": "linux", "machine": "x86_64", "python": "3.11", "backend": "cpu",
    "pallas_interpret": 1, "cpu_count": 4,
}
DEFAULT_HEADROOM = 3.0


def scale_wallclock(obj, factor: float, under_parent: bool = False):
    """Recursively multiply wall-clock leaves (``seconds`` keys and anything
    under a ``us_per_call`` subtree) by ``factor``; everything else copies."""
    if isinstance(obj, dict):
        return {
            k: scale_wallclock(
                v, factor, under_parent or k in WALLCLOCK_PARENTS)
            if not (k in WALLCLOCK_LEAVES and isinstance(v, (int, float)))
            else round(float(v) * factor, 6)
            for k, v in obj.items()
        }
    if isinstance(obj, list):
        return [scale_wallclock(v, factor, under_parent) for v in obj]
    if under_parent and isinstance(obj, (int, float)) and not isinstance(obj, bool):
        return round(float(obj) * factor, 3)
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fresh", required=True,
                    help="benchmark JSON to promote (must embed a runner "
                         "fingerprint)")
    ap.add_argument("--stem", required=True,
                    help="baseline stem, e.g. BENCH_faults")
    ap.add_argument("--baseline-dir", default="benchmarks/baselines")
    ap.add_argument("--hosted", action="store_true",
                    help="bootstrap a provisional baseline for the pinned CI "
                         "runner class instead of this machine's class")
    ap.add_argument("--headroom", type=float, default=DEFAULT_HEADROOM,
                    help="wall-clock inflation factor for --hosted "
                         f"(default {DEFAULT_HEADROOM})")
    args = ap.parse_args(argv)

    with open(args.fresh) as fh:
        data = json.load(fh)
    fp = data.get("runner")
    if not fp:
        print(f"error: {args.fresh} has no 'runner' fingerprint", file=sys.stderr)
        return 1

    if args.hosted:
        src_slug = fingerprint_slug(fp)
        data = scale_wallclock(data, args.headroom)
        data["runner"] = dict(HOSTED_FINGERPRINT)
        data["bootstrap"] = {
            "note": ("provisional hosted-class baseline synthesized from a "
                     f"{src_slug} run; wall-clock leaves inflated "
                     f"{args.headroom}x — replace with a green bench-smoke "
                     "artifact (promote mode) when one exists"),
            "source_slug": src_slug,
        }
        fp = data["runner"]

    slug = fingerprint_slug(fp)
    os.makedirs(args.baseline_dir, exist_ok=True)
    out = os.path.join(args.baseline_dir, f"{args.stem}.{slug}.json")
    with open(out, "w") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")
    print(f"promoted {args.fresh} -> {out}"
          + (" (provisional hosted bootstrap)" if args.hosted else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
