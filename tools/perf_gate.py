#!/usr/bin/env python
"""Noise-aware performance-regression gate over the repo's perf JSON.

Every perf surface in this repo already speaks one-line JSON —
``bench.py`` (tokens/sec, mfu_6nd), ``tools/serve_bench.py`` (tok/s,
TTFT/ITL percentiles), the continuous profiler's ``device_profile``
records (busy ms, per-bucket ms, mfu; obs/device_profile.py), and
driver-wrapped ``BENCH_r<NN>.json`` round archives. This tool turns any
such trajectory into a CI gate::

    # newest-last file list (a bench history; the repo commits none
    # today — tests/fixtures/bench_rounds/ is a synthetic one):
    python tools/perf_gate.py BENCH_r0*.json --key value --key mfu_6nd
    # a serve_bench history file (--out appends one line per run):
    python tools/perf_gate.py serve_hist.jsonl --key value \
        --key itl_ms.p95:lower
    # the trainer's continuous device profiles:
    python tools/perf_gate.py --from-metrics-jsonl metrics.jsonl \
        --key mfu --key bucket_ms.flash_attention:lower

Inputs are positional JSON files in TRAJECTORY ORDER (newest last);
each file may be a single JSON document, a JSONL stream (every line a
sample, in order), or a driver-wrapped round archive (the
``BENCH_r0*.json`` shape — the sample is its ``parsed`` field).
``--from-metrics-jsonl`` reads a trainer/serving metrics stream and
keeps only ``{"record": "device_profile"}`` rows (``--record`` picks a
different type).

**Keys** are dotted paths into each sample (``itl_ms.p95`` descends
nested dicts), with an optional direction suffix — ``:higher`` (more
is better: throughput, mfu) or ``:lower`` (latency, per-bucket ms).
Unsuffixed keys are inferred: names containing ms/latency/itl/ttft/
time/busy gate lower-is-better, everything else higher.

**Baseline math** (shared with tools/bench_trend.py): the baseline is
the MEDIAN of the trailing ``--window`` samples before the newest, and
the noise scale is their MAD (median absolute deviation, scaled by
1.4826 to estimate sigma). The newest sample regresses when it is
worse than the baseline by more than
``max(--max-regress * |baseline|, --mad-factor * 1.4826 * MAD)`` — so
a noisy history widens its own gate instead of flapping, and a tight
history enforces the fractional bound.

Output: ONE JSON summary line (``slo_report``-style). Exit codes:
0 = every key within bounds, 1 = regression, 2 = insufficient history
(fewer than ``--min-history`` samples carrying a key) or unusable
input. Stdlib only — runs in CI next to metrics_report/slo_report with
no jax.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import List, Optional, Tuple

# MAD -> sigma for normally distributed noise; the standard consistency
# constant, spelled out so the gate formula is reproducible by hand.
MAD_SIGMA = 1.4826

# Direction inference tokenizes the key path on ./_ so "tokens_per_sec"
# (higher-better) never trips on the "s"/"ms" latency hints.
_LOWER_BETTER_TOKENS = frozenset((
    "ms", "s", "itl", "ttft", "latency", "busy", "time", "seconds",
    "stall", "blocked", "wait",
))


def median(xs: List[float]) -> float:
    ys = sorted(xs)
    n = len(ys)
    mid = n // 2
    return ys[mid] if n % 2 else (ys[mid - 1] + ys[mid]) / 2.0


def mad(xs: List[float], center: Optional[float] = None) -> float:
    """Median absolute deviation — the robust noise scale (one outlier
    round cannot widen the gate the way a stddev would)."""
    if center is None:
        center = median(xs)
    return median([abs(x - center) for x in xs])


def baseline_stats(history: List[float]) -> Tuple[float, float]:
    """(median, mad) of a trailing window — THE baseline math, imported
    by tools/bench_trend.py so both tools judge a trajectory
    identically."""
    m = median(history)
    return m, mad(history, m)


def parse_key_spec(spec: str) -> Tuple[str, str, str]:
    """``"itl_ms.p95:lower"`` -> (path, direction, display name)."""
    if ":" in spec:
        path, direction = spec.rsplit(":", 1)
        if direction not in ("higher", "lower"):
            raise ValueError(
                f"key direction must be 'higher' or 'lower', got "
                f"{direction!r} in {spec!r}"
            )
    else:
        path = spec
        tokens = re.split(r"[._]", path.lower())
        direction = (
            "lower"
            if any(t in _LOWER_BETTER_TOKENS for t in tokens)
            else "higher"
        )
    return path, direction, spec


def lookup(doc: dict, path: str):
    """Dotted-path descent; None when any hop is absent or non-numeric."""
    cur = doc
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    if isinstance(cur, bool) or not isinstance(cur, (int, float)):
        return None
    return float(cur)


def _docs_from_text(text: str, path: str) -> List[dict]:
    """One file -> ordered sample docs. Accepts a single JSON document,
    a JSONL stream, or the driver round archive whose sample is the
    ``parsed`` field. Torn JSONL tail lines are skipped (a killed run
    must not wedge the gate)."""
    text = text.strip()
    if not text:
        return []
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        docs = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(d, dict):
                docs.append(d)
        if not docs:
            raise ValueError(f"{path}: neither JSON nor JSONL")
        return docs
    if isinstance(doc, list):
        return [d for d in doc if isinstance(d, dict)]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a JSON object")
    if isinstance(doc.get("parsed"), dict):
        return [doc["parsed"]]  # BENCH_r0*.json round archive
    return [doc]


def load_samples(paths: List[str], record: Optional[str] = None,
                 from_jsonl: Optional[str] = None,
                 missing: Optional[List[str]] = None) -> List[dict]:
    """``missing`` (when given) collects paths that do not exist yet —
    an ABSENT history file is the bootstrap state (no bench round has
    appended to it), not a usage error: the caller reports it as
    insufficient history (exit 2), never a traceback."""
    docs: List[dict] = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as fh:
                text = fh.read()
        except FileNotFoundError:
            if missing is None:
                raise
            missing.append(p)
            continue
        docs.extend(_docs_from_text(text, p))
    if from_jsonl:
        want = record or "device_profile"
        try:
            fh = open(from_jsonl, encoding="utf-8")
        except FileNotFoundError:
            if missing is None:
                raise
            missing.append(from_jsonl)
            fh = None
        if fh is not None:
            with fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        d = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(d, dict) and d.get("record") == want:
                        docs.append(d)
    elif record:
        docs = [d for d in docs if d.get("record") == record]
    return docs


def gate_key(samples: List[dict], spec: str, window: int,
             max_regress: float, mad_factor: float,
             min_history: int) -> dict:
    """Judge one key over the trajectory; the per-key summary entry."""
    path, direction, name = parse_key_spec(spec)
    series = [
        (i, v) for i, v in
        ((i, lookup(d, path)) for i, d in enumerate(samples))
        if v is not None
    ]
    out: dict = {"key": name, "path": path, "direction": direction,
                 "n": len(series)}
    if len(series) < min_history:
        out["status"] = "insufficient_history"
        out["min_history"] = min_history
        return out
    values = [v for _, v in series]
    newest = values[-1]
    history = values[:-1][-window:]
    if not history:
        # --min-history 1 with a single sample: nothing to compare
        # against is insufficient history, not a crash
        out["status"] = "insufficient_history"
        out["min_history"] = max(min_history, 2)
        return out
    base, noise = baseline_stats(history)
    slack = max(max_regress * abs(base), mad_factor * MAD_SIGMA * noise)
    delta = (newest - base) if direction == "higher" else (base - newest)
    regressed = delta < -slack
    out.update({
        "status": "regressed" if regressed else "ok",
        "newest": newest,
        "baseline_median": round(base, 6),
        "baseline_mad": round(noise, 6),
        "allowed_slack": round(slack, 6),
        "delta_vs_baseline": round(newest - base, 6),
        "window_n": len(history),
    })
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("files", nargs="*",
                   help="perf JSON files in trajectory order (newest "
                        "LAST); single-doc JSON, JSONL, or BENCH_r* "
                        "round archives")
    p.add_argument("--from-metrics-jsonl", default=None, dest="from_jsonl",
                   help="read device_profile records from a trainer/"
                        "serving metrics.jsonl stream (the spelling "
                        "shared with metrics_report/slo_report)")
    p.add_argument("--record", default=None,
                   help="with --from-metrics-jsonl (or plain JSONL "
                        "inputs): gate this record type instead of "
                        "device_profile")
    p.add_argument("--key", action="append", default=None,
                   help="dotted path into each sample, optional "
                        ":higher/:lower direction suffix (repeat; "
                        "default: value)")
    p.add_argument("--window", type=int, default=5,
                   help="trailing samples (before the newest) forming "
                        "the baseline")
    p.add_argument("--max-regress", type=float, default=0.10,
                   help="fractional regression bound vs the baseline "
                        "median (0.10 = 10%%)")
    p.add_argument("--mad-factor", type=float, default=3.0,
                   help="noise bound: regressions within this many "
                        "MAD-sigmas of the baseline are not gated")
    p.add_argument("--min-history", type=int, default=3,
                   help="samples (including the newest) a key needs "
                        "before it can gate; fewer exits 2")
    args = p.parse_args()

    if not args.files and not args.from_jsonl:
        p.error("give perf JSON files and/or --from-metrics-jsonl")
    missing: List[str] = []
    try:
        samples = load_samples(args.files, record=args.record,
                               from_jsonl=args.from_jsonl,
                               missing=missing)
    except (OSError, ValueError) as e:
        print(json.dumps({"metric": "perf_gate", "error": str(e)}))
        print(f"CHECK FAILED: {e}", file=sys.stderr)
        return 2
    if not samples or missing:
        # the bootstrap state: named history files absent, or every
        # input empty (no bench round has appended yet). One JSON line
        # + exit 2 — never a traceback, distinguishable from a
        # regression (exit 1) so CI treats it as "go run the bootstrap
        # round". A MISSING file fails even when other files yielded
        # samples: silently gating a partial trajectory would pass the
        # very series the absent file was supposed to gate.
        print(json.dumps({
            "metric": "perf_gate",
            "status": "insufficient_history",
            "samples": len(samples),
            "missing_files": missing,
            "hint": "insufficient history, run a bench round "
                    "(bench.py / serve_bench.py --out) to bootstrap "
                    "the trajectory",
            "ok": False,
        }))
        print("CHECK FAILED: insufficient history, run a bench round"
              + (f" (missing: {', '.join(missing)})" if missing else ""),
              file=sys.stderr)
        return 2
    specs = args.key or ["value"]
    try:
        keys = [
            gate_key(samples, spec, args.window, args.max_regress,
                     args.mad_factor, args.min_history)
            for spec in specs
        ]
    except ValueError as e:
        print(json.dumps({"metric": "perf_gate", "error": str(e)}))
        print(f"CHECK FAILED: {e}", file=sys.stderr)
        return 2
    regressed = [k["key"] for k in keys if k["status"] == "regressed"]
    insufficient = [
        k["key"] for k in keys if k["status"] == "insufficient_history"
    ]
    summary = {
        "metric": "perf_gate",
        "samples": len(samples),
        "window": args.window,
        "max_regress": args.max_regress,
        "mad_factor": args.mad_factor,
        "keys": keys,
        "regressed": regressed,
        "insufficient": insufficient,
        "ok": not regressed and not insufficient,
    }
    print(json.dumps(summary))
    for k in keys:
        if k["status"] == "regressed":
            print(
                f"CHECK FAILED: {k['key']} regressed — newest "
                f"{k['newest']} vs baseline median "
                f"{k['baseline_median']} (allowed slack "
                f"{k['allowed_slack']})", file=sys.stderr,
            )
        elif k["status"] == "insufficient_history":
            print(
                f"CHECK FAILED: {k['key']} has {k['n']} samples, needs "
                f"{k['min_history']} (insufficient history)",
                file=sys.stderr,
            )
    if regressed:
        return 1
    if insufficient:
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
