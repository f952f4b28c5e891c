#!/usr/bin/env python
"""Render a bench trajectory as one machine-readable line.

A round archive is bench.py's JSON line wrapped by whoever ran it:
``BENCH_r<NN>.json`` = ``{"rc", "tail", "parsed"}`` (single-chip train
step: tokens/sec, vs_baseline, mfu_6nd, and the run's final loss in the
stderr ``tail``), with ``MULTICHIP_r<NN>.json`` (the 8-virtual-device
dry-run result) alongside. This tool reads such a history and prints
ONE JSON summary line, so "are we still getting faster round over
round?" is a jq expression instead of five file opens. No chip rounds
are committed today (tests/fixtures/bench_rounds/ holds a synthetic
trajectory for the tests)::

    python tools/bench_trend.py BENCH_r0*.json   # explicit round files
    python tools/bench_trend.py --ascii BENCH_r0*.json  # + sparklines
    python tools/bench_trend.py                  # repo-root BENCH_r*/MULTICHIP_r*

Baseline math is IMPORTED from tools/perf_gate.py (median + MAD over
the trailing window) so this trend view and the CI gate judge a
trajectory identically — the summary's per-series ``baseline`` block is
exactly what ``perf_gate.py --key`` would gate the next round against.
Stdlib only.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from perf_gate import baseline_stats  # noqa: E402  (shared gate math)

_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(xs: List[Optional[float]]) -> str:
    vals = [x for x in xs if x is not None]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    out = []
    for x in xs:
        if x is None:
            out.append(" ")
        else:
            out.append(_SPARK[int((x - lo) / span * (len(_SPARK) - 1))])
    return "".join(out)


def _round_of(path: str) -> int:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else 0


def load_round(path: str) -> Optional[dict]:
    """One round archive -> its summary row, or None for an absent,
    empty, or torn file (a killed bench run's half-written archive
    must degrade to 'that round is missing', never a traceback)."""
    try:
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    out = {
        "round": _round_of(path),
        "file": os.path.basename(path),
        "rc": doc.get("rc") if isinstance(doc, dict) else None,
    }
    if isinstance(parsed, dict):
        out["value"] = parsed.get("value")
        out["vs_baseline"] = parsed.get("vs_baseline")
        out["mfu_6nd"] = parsed.get("mfu_6nd")
    # the run's final training loss only appears in the archived stderr
    # tail ("loss=9.0810"); a missing tail degrades to None
    tail = doc.get("tail", "") if isinstance(doc, dict) else ""
    m = re.search(r"loss=([0-9.]+)", tail or "")
    out["loss"] = float(m.group(1).rstrip(".")) if m else None
    return out


def _series(rounds: List[dict], key: str) -> List[Optional[float]]:
    return [r.get(key) for r in rounds]


def _baseline(series: List[Optional[float]], window: int) -> Optional[dict]:
    vals = [v for v in series if v is not None]
    if len(vals) < 2:
        return None
    med, noise = baseline_stats(vals[-window:])
    return {"median": round(med, 4), "mad": round(noise, 4),
            "window_n": min(window, len(vals))}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("files", nargs="*",
                   help="round archives (default: BENCH_r*.json next "
                        "to the repo root, MULTICHIP_r*.json alongside)")
    p.add_argument("--multichip", action="append", default=None,
                   help="MULTICHIP round archives (default: globbed "
                        "beside the BENCH files)")
    p.add_argument("--window", type=int, default=5,
                   help="trailing rounds forming the baseline block "
                        "(perf_gate math)")
    p.add_argument("--ascii", action="store_true",
                   help="also draw per-series sparklines on stderr")
    args = p.parse_args()

    def _insufficient(detail):
        # bootstrap state (absent/empty/torn history): one JSON line +
        # exit 2, the same contract as tools/perf_gate.py — never a
        # traceback, distinguishable from a real trend failure
        print(json.dumps({
            "metric": "bench_trend",
            "status": "insufficient_history",
            "detail": detail,
            "hint": "insufficient history, run a bench round "
                    "(bench.py) to bootstrap the trajectory",
            "ok": False,
        }))
        print("CHECK FAILED: insufficient history, run a bench round",
              file=sys.stderr)
        return 2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_files = args.files or sorted(
        glob.glob(os.path.join(root, "BENCH_r*.json")), key=_round_of
    )
    if not bench_files:
        return _insufficient("no BENCH_r*.json rounds found")
    bench_files = sorted(bench_files, key=_round_of)
    multichip_files = sorted(
        args.multichip if args.multichip is not None else
        glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(bench_files[0])) or ".",
            "MULTICHIP_r*.json",
        )),
        key=_round_of,
    )

    rounds = [r for r in (load_round(p_) for p_ in bench_files)
              if r is not None]
    if not rounds:
        return _insufficient(
            f"{len(bench_files)} BENCH file(s) named but none "
            "readable (absent, empty, or torn)"
        )
    series = {
        key: _series(rounds, key)
        for key in ("value", "vs_baseline", "mfu_6nd", "loss")
    }
    multichip_ok = []
    for p_ in multichip_files:
        try:
            with open(p_, encoding="utf-8") as fh:
                doc = json.load(fh)
            multichip_ok.append(bool(doc.get("ok")))
        except (OSError, json.JSONDecodeError):
            multichip_ok.append(False)

    summary = {
        "metric": "bench_trend",
        "rounds": [r["round"] for r in rounds],
        "tokens_per_sec": series["value"],
        "vs_baseline": series["vs_baseline"],
        "mfu_6nd": series["mfu_6nd"],
        "loss": series["loss"],
        "multichip_ok": multichip_ok,
        # perf_gate's exact baseline math over the same window: what
        # the NEXT round will be judged against
        "baseline": {
            key: _baseline(series[key], args.window)
            for key in ("value", "vs_baseline", "mfu_6nd")
        },
    }
    print(json.dumps(summary))
    if args.ascii:
        for key in ("value", "vs_baseline", "mfu_6nd", "loss"):
            vals = series[key]
            shown = [f"{v:g}" if v is not None else "-" for v in vals]
            print(f"[bench_trend] {key:14s} {sparkline(vals)}  "
                  f"({' '.join(shown)})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
