"""Serving throughput/latency bench: closed-loop load against the
continuous-batching engine (differential_transformer_replication_tpu/
serving/).

``--clients`` worker threads each run a closed loop — submit one
request, wait for completion, submit the next — through the in-process
``ServingClient``, so concurrency equals the client count and the
engine's iteration-level scheduler batches across them. With ``--http``
the same closed loop runs over the stdlib HTTP server on an ephemeral
port instead. Prompt lengths are drawn uniformly from
[--min-prompt, --max-prompt] with a fixed seed, so runs are comparable.

Either way each worker retries RETRIABLE failures (queue-full 503s,
engine crashes mid-restart) with jittered exponential backoff honoring
the server's Retry-After (serving/retry.py), and the bench reports an
``errors`` breakdown — queue_full / engine_crash / deadline / timeout
counts plus total retries — instead of silently folding failures into
the latency stats.

Prints ONE JSON line (like bench.py) with requests/sec, output
tokens/sec, and p50/p95 time-to-first-token + inter-token latency, e.g.::

    {"metric": "serving_output_tokens_per_sec", "value": ..., ...}

``--smoke`` shrinks everything (tiny random-init model, few requests)
so the whole run completes in seconds under ``JAX_PLATFORMS=cpu`` —
exercised by tests/test_serving.py as the quick-tier smoke.

By default the model is RANDOM-INIT at the requested shape (throughput
does not depend on trained weights); pass --checkpoint to serve real
weights instead.

``--target URL`` (repeatable) drives the SAME closed loop against
already-running servers instead of an in-process one — point it at N
replica URLs (round-robin) or at one router URL (serving/router.py);
no jax is imported and no local engine is built. The JSON summary then
carries a ``per_replica`` breakdown (req/s, errors, retries, hedges)
keyed by the router's per-response ``replica`` attribution (or by
target URL when driving replicas directly), so router fairness is
measurable: a healthy 2-replica fleet should show ~equal req/s per
replica and aggregate ≥ 1.7x one replica at equal per-replica config.
Warmup posts the prefill-chunk ladder to every ``--target`` first so
remote first-compiles stay out of the measured window (warming a
router warms whichever replicas it picks; warm replicas directly for
strict pins).

Every bench run reports ``slow_exemplars``: the trace ids of the ~10
slowest-TTFT requests (in-process requests mint their own trace
contexts; --target replies carry the router/replica-minted id), so a
latency regression is ONE command away from its fleet-wide timeline::

    python tools/trace_stitch.py router.trace.json replica-*.trace.json \
        -o slow.json --trace-id <slow_exemplars[0].trace_id>

``--trace-dir`` makes the in-process engine write its span trace
there; in --target mode it names where the fleet's own --trace-path
files live and rides into the JSON line so the stitch command needs no
guessing.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _percentiles(xs, ps=(50, 95)):
    if not xs:
        return {f"p{p}": None for p in ps}
    return {f"p{p}": round(float(np.percentile(xs, p)), 3) for p in ps}


def _slow_exemplars(completed, n=10):
    """Trace ids of the ~p99 tail: the slowest-TTFT requests, so a
    bench regression is directly looked up in the stitched timeline
    (tools/trace_stitch.py --trace-id <id>). ``completed`` entries are
    (tokens, ttft_ms, itls, trace_id[, priority]); untraced requests
    are skipped."""
    tail = sorted(
        (e for e in completed if e[3]),
        key=lambda e: e[1], reverse=True,
    )[:n]
    return [
        {"trace_id": e[3], "ttft_ms": round(float(e[1]), 3)}
        for e in tail
    ]


def _parse_priority_mix(spec):
    """``high:8,batch:56`` -> ``{"high": 8, "batch": 56}``."""
    if not spec:
        return None
    mix = {}
    for part in spec.split(","):
        cls, _, n = part.partition(":")
        cls = cls.strip()
        if cls not in ("high", "normal", "batch"):
            raise SystemExit(
                f"--priority-mix: unknown class {cls!r} "
                "(want high/normal/batch)"
            )
        try:
            count = int(n)
        except ValueError:
            raise SystemExit(f"--priority-mix: bad count in {part!r}")
        if count < 1:
            raise SystemExit(
                f"--priority-mix: count must be >= 1 in {part!r}"
            )
        mix[cls] = mix.get(cls, 0) + count
    return mix


def _run_against_targets(args, targets, post) -> None:
    """Closed-loop HTTP load against already-running servers (replica
    URLs round-robin, or one router URL). No jax, no local engine —
    this path must be runnable from an operator laptop at a live
    fleet. Reports the same JSON line as the in-process bench plus a
    ``per_replica`` breakdown keyed by response attribution."""
    import random as _random

    rng = np.random.default_rng(args.seed)
    max_prompt = max(1, args.max_prompt)
    min_prompt = min(args.min_prompt, max_prompt)
    prompts = [
        rng.integers(
            0, args.vocab_size,
            size=int(rng.integers(min_prompt, max_prompt + 1)),
        ).tolist()
        for _ in range(args.requests)
    ]

    # warmup: post the prefill-chunk ladder to every target so remote
    # first-compiles stay out of the measured window (a router target
    # warms whichever replicas its picker chooses)
    ladder, size = [], 1
    while size <= min(args.prefill_chunk, max_prompt):
        ladder.append(size)
        size *= 2
    for url in targets:
        for n in ladder:
            try:
                post(url, {"prompt_ids": [1] * n, "max_new_tokens": 2,
                           "temperature": args.temperature, "seed": 0},
                     timeout=600, max_retries=args.max_retries)
            except (OSError, ValueError) as e:
                print(f"[serve_bench] warmup against {url} failed: {e!r}",
                      file=sys.stderr)

    completed = []
    errors = {"queue_full": 0, "engine_crash": 0, "deadline": 0,
              "timeout": 0, "shutting_down": 0, "no_replica": 0,
              "other": 0}
    per_replica: dict = {}
    retries_total = [0]
    hedges_total = [0]
    migrated_total = [0]   # replies stitched after live migration
    replayed_total = [0]   # replies reconstructed via resume-by-replay
    lock = threading.Lock()
    next_idx = [0]

    def _acct(key):
        entry = per_replica.get(key)
        if entry is None:
            entry = per_replica[key] = {
                "ok": 0, "errors": 0, "retries": 0, "hedges": 0,
            }
        return entry

    def _code_bucket(body):
        code = (body or {}).get("code", "")
        if code == "shutting_down":
            return "shutting_down"
        if code in ("engine_crash", "engine_failed"):
            return "engine_crash"
        if code == "timeout":
            return "timeout"
        if code == "queue_full":
            return "queue_full"
        if code in ("no_replica", "replica_unreachable"):
            return "no_replica"
        return "other"

    def worker(wid):
        rng_w = _random.Random(args.seed * 1000 + wid)
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(prompts):
                    return
                next_idx[0] += 1
            url = targets[i % len(targets)]
            payload = {
                "prompt_ids": prompts[i],
                "max_new_tokens": args.new_tokens,
                "temperature": args.temperature,
                "seed": args.seed + i,
                "timeout": 600,
            }
            if args.deadline:
                payload["deadline_s"] = args.deadline
            try:
                status, body, retries = post(
                    url, payload, timeout=600,
                    max_retries=args.max_retries, rng=rng_w,
                    deadline_s=args.deadline or None,
                )
            except (OSError, ValueError) as e:  # transport dead (or
                # serving garbage bodies) past the retry budget
                with lock:
                    errors["no_replica"] += 1
                    r = getattr(e, "retry_attempts", 0)
                    retries_total[0] += r
                    entry = _acct(url)
                    entry["errors"] += 1
                    entry["retries"] += r
                continue
            # attribution: the router stamps each reply with the
            # replica that served it; direct replicas key by target
            key = (body or {}).get("replica") or url
            with lock:
                retries_total[0] += retries
                entry = _acct(key)
                entry["retries"] += retries
                if (body or {}).get("hedged"):
                    hedges_total[0] += 1
                    entry["hedges"] += 1
                if status == 200:
                    completed.append(
                        (len(body["tokens"]), body["ttft_ms"], [],
                         body.get("trace_id"))
                    )
                    entry["ok"] += 1
                    if body.get("migrated"):
                        migrated_total[0] += 1
                    if body.get("replayed"):
                        replayed_total[0] += 1
                else:
                    entry["errors"] += 1
                    if status == 504:
                        errors["deadline"] += 1
                    elif status == 503:
                        errors[_code_bucket(body)] += 1
                    else:
                        errors["other"] += 1

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=worker, args=(w,))
        for w in range(args.clients)
    ]
    for t in threads:
        t.start()
    drain_result = [None]
    drain_thread = None
    if getattr(args, "drain_during_run", None):
        # zero-loss-failover arm: mid-run, ask the ROUTER (the first
        # target) to live-migrate one replica's in-flight decodes to
        # its peers; the load threads never notice beyond the stitched
        # migrated/replayed replies counted above
        def _drain():
            time.sleep(max(0.0, args.drain_delay_s))
            try:
                status, body, _ = post(
                    targets[0].rstrip("/") + "/drain",
                    {"replica": args.drain_during_run},
                    timeout=600, max_retries=0,
                )
                drain_result[0] = (
                    body if status == 200
                    else {"status": status,
                          "error": (body or {}).get("error")}
                )
            except (OSError, ValueError) as e:
                drain_result[0] = {"error": repr(e)}

        drain_thread = threading.Thread(target=_drain, daemon=True)
        drain_thread.start()
    for t in threads:
        t.join()
    if drain_thread is not None:
        drain_thread.join(30.0)
    wall = time.perf_counter() - t0

    out_tokens = sum(e[0] for e in completed)
    ttfts_ms = [e[1] for e in completed]
    n_failed = sum(errors.values())
    for entry in per_replica.values():
        entry["req_per_s"] = round(entry["ok"] / wall, 3)
    line = {
        "metric": "serving_output_tokens_per_sec",
        "value": round(out_tokens / wall, 1),
        "unit": "tokens/sec",
        "requests_per_sec": round(len(completed) / wall, 3),
        "ttft_ms": _percentiles(ttfts_ms),
        "itl_ms": _percentiles([]),
        "n_requests": len(completed),
        "errors": errors,
        "retries": retries_total[0],
        "hedges": hedges_total[0],
        "migrated": migrated_total[0],
        "replayed": replayed_total[0],
        "failed": n_failed,
        "output_tokens": out_tokens,
        "wall_s": round(wall, 3),
        "slow_exemplars": _slow_exemplars(completed),
        "trace_dir": args.trace_dir,
        "per_replica": per_replica,
        "targets": targets,
        "clients": args.clients,
        "new_tokens": args.new_tokens,
        "prompt_len_range": [min_prompt, max_prompt],
        "http": True,
        "smoke": bool(args.smoke),
    }
    if drain_thread is not None:
        line["drain"] = drain_result[0] or {"error": "drain timed out"}
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] targets={len(targets)} clients={args.clients} "
        f"reqs={len(completed)} failed={n_failed} "
        f"migrated={migrated_total[0]} replayed={replayed_total[0]} "
        f"retries={retries_total[0]} hedges={hedges_total[0]} "
        f"wall={wall:.2f}s out_tok/s={out_tokens / wall:.1f} "
        f"per_replica={json.dumps(per_replica)}",
        file=sys.stderr,
    )
    assert len(completed) + n_failed == args.requests, \
        "some requests neither completed nor failed"


def make_diurnal_schedule(duration_s: float, low_rps: float,
                          high_rps: float) -> list:
    """Arrival offsets (seconds from start) over ONE diurnal cycle:
    the instantaneous rate follows a raised cosine from ``low_rps``
    (t=0) up to ``high_rps`` (t=duration/2) and back down, with
    arrivals stepped deterministically at 1/rate(t) — the same
    schedule every run, no sampling noise."""
    if duration_s <= 0:
        raise ValueError(f"duration must be > 0, got {duration_s}")
    if low_rps < 0 or high_rps < low_rps:
        raise ValueError(
            f"want 0 <= low <= high, got {low_rps}..{high_rps}"
        )
    out: list = []
    t = 0.0
    while True:
        rate = low_rps + (high_rps - low_rps) * 0.5 * (
            1.0 - math.cos(2.0 * math.pi * t / duration_s)
        )
        t += 1.0 / max(rate, 1e-3)
        if t >= duration_s:
            return out
        out.append(t)


def load_trace_schedule(spec: str) -> list:
    """``--trace`` input: ``diurnal:DURATION:LOW:HIGH`` synthesizes one
    cosine cycle; anything else is a JSONL file of ``{"t": <seconds
    from start>}`` rows (extra fields ignored, torn lines skipped),
    sorted defensively so a hand-edited trace still replays in
    order."""
    if spec.startswith("diurnal:"):
        parts = spec.split(":")
        if len(parts) != 4:
            raise SystemExit(
                f"--trace: want diurnal:DURATION:LOW:HIGH, got {spec!r}"
            )
        try:
            return make_diurnal_schedule(
                float(parts[1]), float(parts[2]), float(parts[3])
            )
        except ValueError as e:
            raise SystemExit(f"--trace: {e}")
    sched = []
    with open(spec, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and "t" in row:
                sched.append(float(row["t"]))
    if not sched:
        raise SystemExit(f"--trace {spec}: no timestamped rows")
    return sorted(sched)


def _run_trace_replay(args, targets, post) -> None:
    """Open-loop timestamped replay (``--trace``) against a live
    fleet: each request fires AT its scheduled instant whether or not
    earlier ones finished (a closed loop hides overload by slowing its
    own offered rate — useless for judging shedding or autoscaling).
    Reports per-window offered/served/shed rates and TTFT SLO burn,
    plus the replicas-in-rotation timeline polled from the router's
    ``/health`` — the replica-hours integral the autoscaler acceptance
    compares against a static fleet. With ``--clients`` workers, an
    overloaded fleet delays arrivals rather than dropping them
    (bounded open loop); sheds and transport failures count as SLO-bad
    in their scheduled window."""
    import random as _random
    import urllib.request

    schedule = load_trace_schedule(args.trace)
    rng = np.random.default_rng(args.seed)
    max_prompt = max(1, args.max_prompt)
    min_prompt = min(args.min_prompt, max_prompt)
    prompts = [
        rng.integers(
            0, args.vocab_size,
            size=int(rng.integers(min_prompt, max_prompt + 1)),
        ).tolist()
        for _ in range(len(schedule))
    ]

    ladder, size = [], 1
    while size <= min(args.prefill_chunk, max_prompt):
        ladder.append(size)
        size *= 2
    for url in targets:
        for n in ladder:
            try:
                post(url, {"prompt_ids": [1] * n, "max_new_tokens": 2,
                           "temperature": args.temperature, "seed": 0},
                     timeout=600, max_retries=args.max_retries)
            except (OSError, ValueError) as e:
                print(f"[serve_bench] warmup against {url} failed: "
                      f"{e!r}", file=sys.stderr)

    results = []  # (scheduled_t, "ok" | "shed", ttft_ms | None)
    lock = threading.Lock()
    next_idx = [0]
    stop = threading.Event()
    # replicas-in-rotation timeline: the router's /health (eligible
    # count) sampled through the run; replica_seconds integrates it
    health_url = targets[0][: -len("/generate")] + "/health"
    timeline = []  # (t_offset_s, eligible | -1 for a failed sample)
    t0 = time.perf_counter()

    def poll_replicas():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(health_url, timeout=2) as r:
                    h = json.load(r)
                eligible = int(h.get("eligible", 0))
            except (OSError, ValueError):
                eligible = -1
            timeline.append(
                (round(time.perf_counter() - t0, 3), eligible)
            )
            stop.wait(0.5)

    def worker(wid):
        rng_w = _random.Random(args.seed * 1000 + wid)
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(schedule):
                    return
                next_idx[0] += 1
            delay = (t0 + schedule[i]) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            payload = {
                "prompt_ids": prompts[i],
                "max_new_tokens": args.new_tokens,
                "temperature": args.temperature,
                "seed": args.seed + i,
                "timeout": 600,
            }
            if args.deadline:
                payload["deadline_s"] = args.deadline
            try:
                status, body, _retries = post(
                    targets[i % len(targets)], payload, timeout=600,
                    max_retries=args.max_retries, rng=rng_w,
                    deadline_s=args.deadline or None,
                )
            except (OSError, ValueError):
                with lock:
                    results.append((schedule[i], "shed", None))
                continue
            with lock:
                if status == 200:
                    results.append(
                        (schedule[i], "ok", body["ttft_ms"])
                    )
                else:
                    results.append((schedule[i], "shed", None))

    poller = threading.Thread(target=poll_replicas, daemon=True)
    poller.start()
    threads = [
        threading.Thread(target=worker, args=(w,))
        for w in range(args.clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stop.set()
    poller.join(3.0)

    # windowed judgment: a shed or transport failure is SLO-BAD in its
    # scheduled window (honest backpressure still spent error budget)
    window_s = max(0.1, args.trace_window)
    duration = schedule[-1] if schedule else 0.0
    n_windows = int(duration // window_s) + 1
    ttft_bound_ms = args.ttft_slo * 1000.0
    budget = max(1e-9, 1.0 - args.slo_target)
    windows = []
    for w in range(n_windows):
        windows.append({
            "t_start": round(w * window_s, 3),
            "t_end": round((w + 1) * window_s, 3),
            "offered": 0, "served": 0, "shed": 0, "_ttfts": [],
        })
    for sched_t, kind, ttft in results:
        w = windows[min(n_windows - 1, int(sched_t // window_s))]
        w["offered"] += 1
        if kind == "ok":
            w["served"] += 1
            w["_ttfts"].append(ttft)
        else:
            w["shed"] += 1
    violating = 0
    burn_timeline = []
    for w in windows:
        ttfts = w.pop("_ttfts")
        slow = sum(1 for v in ttfts if v > ttft_bound_ms)
        w["req_per_s"] = round(w["offered"] / window_s, 3)
        w["shed_rate"] = (
            None if w["offered"] == 0
            else round(w["shed"] / w["offered"], 4)
        )
        w["ttft_p95_ms"] = _percentiles(ttfts)["p95"]
        err = (
            None if w["offered"] == 0
            else (slow + w["shed"]) / w["offered"]
        )
        w["burn"] = None if err is None else round(err / budget, 3)
        if w["burn"] is not None and w["burn"] > 1.0:
            violating += 1
        burn_timeline.append((w["t_start"], w["burn"]))
    good_samples = [
        (t, n) for t, n in timeline if n >= 0
    ]
    replica_seconds = 0.0
    for j, (t, n) in enumerate(good_samples):
        t_next = (
            good_samples[j + 1][0] if j + 1 < len(good_samples)
            else wall
        )
        replica_seconds += n * max(0.0, t_next - t)
    served = sum(w["served"] for w in windows)
    shed = sum(w["shed"] for w in windows)
    offered = sum(w["offered"] for w in windows)
    line = {
        "metric": "serving_trace_replay",
        "value": round(replica_seconds / 3600.0, 6),
        "unit": "replica_hours",
        "replica_seconds": round(replica_seconds, 3),
        "offered": offered,
        "served": served,
        "shed": shed,
        "shed_rate": None if not offered else round(shed / offered, 4),
        "violating_windows": violating,
        "windows": windows,
        "burn_timeline": burn_timeline,
        "replica_timeline": timeline,
        "ttft_slo_s": args.ttft_slo,
        "slo_target": args.slo_target,
        "window_s": window_s,
        "trace": args.trace,
        "wall_s": round(wall, 3),
        "targets": targets,
        "clients": args.clients,
        "http": True,
        "smoke": bool(args.smoke),
    }
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] trace replay: offered={offered} served={served} "
        f"shed={shed} violating_windows={violating}/{n_windows} "
        f"replica_hours={line['value']} wall={wall:.2f}s",
        file=sys.stderr,
    )
    assert served + shed == offered == len(schedule), \
        "some scheduled requests neither completed nor failed"


def _run_shared_prefix(args, client, engine, serving, model_cfg,
                       tracer) -> None:
    """``--shared-prefix N:M`` workload: N sessions sharing one M-token
    system prompt, against the paged engine's radix prefix cache
    (serving/pages.py). Two sequential measured phases — "miss" (N
    requests with UNIQUE M-token prefixes, every prefill cold) and
    "hit" (N requests sharing the primed M-token prefix, prefill skips
    the cached pages) — report TTFT split by cache-hit/miss plus the
    pool's measured ``prefix_cache_hit_rate`` in the one JSON line.
    Requests run one at a time so TTFT is pure prefill+first-token
    work, not queue wait; the whole measured window rides under the
    RecompileSentinel (page churn and COW forks must compile NOTHING).
    """
    import numpy as _np

    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )
    from differential_transformer_replication_tpu.models.decode import (
        kv_store_dtype,
    )

    n_sessions, m_prefix = (int(x) for x in args.shared_prefix.split(":"))
    if n_sessions < 1 or m_prefix < 1:
        raise SystemExit("--shared-prefix wants N:M with N,M >= 1")
    V = model_cfg.vocab_size
    rng = np.random.default_rng(args.seed)
    tail_lo = max(1, args.min_prompt)
    tail_hi = max(tail_lo, args.max_prompt)
    limit = model_cfg.block_size - args.new_tokens - tail_hi
    if m_prefix > limit:
        raise SystemExit(
            f"--shared-prefix prefix ({m_prefix}) + max tail "
            f"({tail_hi}) + new tokens ({args.new_tokens}) exceeds "
            f"block_size ({model_cfg.block_size}); shrink M"
        )

    def _tail():
        return rng.integers(
            0, V, size=int(rng.integers(tail_lo, tail_hi + 1))
        ).tolist()

    shared = rng.integers(0, V, size=m_prefix).tolist()
    miss_prompts = [
        rng.integers(0, V, size=m_prefix).tolist() + _tail()
        for _ in range(n_sessions)
    ]
    hit_prompts = [shared + _tail() for _ in range(n_sessions)]

    # warmup: the prefill pow-2 ladder, the decode/sample steps, AND
    # one COW fork (two warm prompts sharing a non-page-aligned
    # prefix) so the measured phases compile nothing
    ladder, size = [], 1
    # cap at the LONGEST measured prompt: chunks up to prefill_chunk
    # appear whenever a prompt reaches that length, and a chunk shape
    # first compiled inside the sentinel window fails the bench
    while size <= min(serving.prefill_chunk, m_prefix + tail_hi):
        ladder.append(size)
        size *= 2
    for j, n in enumerate(ladder):
        # DISTINCT first token per ladder size: every radix match
        # (full page or partial fork) must match position 0 first, so
        # differing first tokens guarantee each warm prompt misses the
        # cache and actually compiles its chunk shape — a warm prompt
        # that hit an earlier entry's cached prefix would skip the
        # very chunk this ladder exists to compile
        prompt = [j % V] + rng.integers(0, V, size=n - 1).tolist()
        client.generate(prompt[:n], max_new_tokens=2,
                        temperature=args.temperature, seed=0,
                        timeout=600)
    warm_pref = (
        [(len(ladder)) % V]
        + rng.integers(0, V, size=serving.kv_page_size).tolist()
    )
    client.generate(warm_pref + [2], max_new_tokens=2,
                    temperature=args.temperature, seed=0, timeout=600)
    client.generate(warm_pref + [3, 4], max_new_tokens=2,
                    temperature=args.temperature, seed=0, timeout=600)

    def _phase(prompts, base_seed):
        ttfts, toks = [], 0
        for i, prompt in enumerate(prompts):
            out = client.generate(
                prompt, max_new_tokens=args.new_tokens,
                temperature=args.temperature, seed=base_seed + i,
                timeout=600,
            )
            ttfts.append(out.ttft * 1e3)
            toks += len(out.tokens)
        return ttfts, toks

    sentinel = RecompileSentinel(
        budget=None if args.allow_recompiles < 0 else args.allow_recompiles,
        name="serve-bench-shared-prefix-window",
    )
    with sentinel:
        t0 = time.perf_counter()
        st0 = engine.page_stats()
        miss_ttfts, miss_tok = _phase(miss_prompts, args.seed)
        # prime the shared prefix once (a miss, excluded from the hit
        # phase's stats window)
        client.generate(shared + _tail(), max_new_tokens=2,
                        temperature=args.temperature, seed=1,
                        timeout=600)
        st1 = engine.page_stats()
        hit_ttfts, hit_tok = _phase(hit_prompts, args.seed + 10_000)
        st2 = engine.page_stats()
        wall = time.perf_counter() - t0
    client.close()
    if tracer is not None:
        tracer.close()

    hit_phase = st2["hits_total"] - st1["hits_total"]
    hit_rate = hit_phase / max(1, n_sessions)
    out_tokens = miss_tok + hit_tok
    med_miss = float(_np.median(miss_ttfts))
    med_hit = float(_np.median(hit_ttfts))
    line = {
        "metric": "serving_output_tokens_per_sec",
        "value": round(out_tokens / wall, 1),
        "unit": "tokens/sec",
        "ttft_ms": _percentiles(miss_ttfts + hit_ttfts),
        "ttft_ms_miss": _percentiles(miss_ttfts),
        "ttft_ms_hit": _percentiles(hit_ttfts),
        "ttft_hit_over_miss": (
            round(med_hit / med_miss, 3) if med_miss > 0 else None
        ),
        "prefix_cache_hit_rate": round(hit_rate, 3),
        "shared_prefix": {"sessions": n_sessions, "prefix_len": m_prefix},
        "kv_pages": st2,
        "kv_page_size": serving.kv_page_size,
        "kv_pool_pages": st2["total"],
        "n_requests": 2 * n_sessions,
        "output_tokens": out_tokens,
        "wall_s": round(wall, 3),
        "compiles_in_window": sentinel.count,
        "model": model_cfg.model,
        "decode_attention_impl": engine.cfg.decode_attention_impl,
        "kv_cache_dtype": kv_store_dtype(engine.cfg),
        "num_slots": serving.num_slots,
        "new_tokens": args.new_tokens,
        "smoke": bool(args.smoke),
    }
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] shared-prefix {n_sessions}:{m_prefix} "
        f"hit_rate={hit_rate:.2f} ttft_miss_p50={med_miss:.1f}ms "
        f"ttft_hit_p50={med_hit:.1f}ms "
        f"(hit/miss={line['ttft_hit_over_miss']}) "
        f"compiles={sentinel.count} pages={st0['total']}",
        file=sys.stderr,
    )


def spec_workload(client, prompts, new_tokens, clients, seed,
                  temperature):
    """Closed-loop greedy/sampled workload driver shared by the spec
    A/B (below) and tools/spec_sweep.py: N worker threads drain the
    prompt list through ``client.generate``. Returns ``(wall seconds,
    total output tokens, {prompt index: tokens})``."""
    completed = {}
    lock = threading.Lock()
    next_idx = [0]

    def worker():
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(prompts):
                    return
                next_idx[0] += 1
            out = client.generate(
                prompts[i], max_new_tokens=new_tokens,
                temperature=temperature, seed=seed + i, timeout=600,
            )
            with lock:
                completed[i] = out.tokens

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    assert len(completed) == len(prompts), "requests went missing"
    return wall, sum(len(t) for t in completed.values()), completed


def _run_spec_ab(args, params, model_cfg, serving) -> None:
    """``--spec MODE`` workload: the SAME closed-loop load run twice —
    a non-spec baseline, then with speculative decoding — against
    fresh engines at otherwise identical config, reported as one JSON
    line (``spec_tok_per_s`` vs ``baseline_tok_per_s``, measured
    ``spec_acceptance_rate``, ``spec_speedup``). Each arm runs the
    workload ONCE unmeasured (compiling every shape the load can
    produce — the jitted closures are module-cached, so they survive
    the fresh measured engine) and then ONCE measured under the
    RecompileSentinel: ``compiles_in_window`` is the spec arm's pin.
    Greedy traffic (--temperature 0) keeps the spec arm bit-identical
    to the baseline; the bench asserts that token-for-token."""
    import jax  # noqa: F401  (engine stack below pulls it in anyway)

    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )
    from differential_transformer_replication_tpu.models.decode import (
        kv_store_dtype,
    )
    from differential_transformer_replication_tpu.serving import (
        ServingClient,
        ServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    # keep the whole request in-window so drafts stay eligible
    # (the verify block must not roll the ring)
    max_prompt = min(args.max_prompt,
                     model_cfg.block_size - args.new_tokens - 1)
    min_prompt = max(1, min(args.min_prompt, max_prompt))
    # templated-traffic profile: each prompt repeats a short random
    # cycle — the repetitive structure (system prompts, code, JSON
    # scaffolding) that speculative decoding exists to exploit and
    # that greedy decoding then perpetuates. Both arms see the SAME
    # prompts, so the A/B isolates the spec machinery.
    prompts = []
    for _ in range(args.requests):
        n = int(rng.integers(min_prompt, max_prompt + 1))
        period = int(rng.integers(2, min(5, n + 1)))
        cyc = rng.integers(0, model_cfg.vocab_size, size=period).tolist()
        prompts.append((cyc * (n // period + 1))[:n])

    def _drafter():
        if args.spec != "model":
            return None
        if not args.spec_drafter_ckpt:
            raise SystemExit(
                "--spec model needs --spec-drafter-ckpt (a checkpoint "
                "dir, or the literal 'self')"
            )
        if args.spec_drafter_ckpt == "self":
            return (params, model_cfg)
        from differential_transformer_replication_tpu.train.checkpoint import (
            load_params_for_inference,
        )

        d_params, d_cfg, _ = load_params_for_inference(
            args.spec_drafter_ckpt
        )
        return (d_params, d_cfg)

    def _workload(client):
        return spec_workload(client, prompts, args.new_tokens,
                             args.clients, args.seed, args.temperature)

    def _arm(spec_on):
        cfg_arm = serving.replace(
            spec_mode=args.spec if spec_on else "",
            spec_draft_len=args.spec_draft_len,
            spec_verify=args.spec_verify,
        )
        drafter = _drafter() if spec_on else None
        # unmeasured warm pass: compiles every shape this exact load
        # produces (prefill ladder, both decode rungs, samplers);
        # module-cached closures carry them to the measured engine
        warm = ServingClient(ServingEngine(
            params, model_cfg, cfg_arm, spec_drafter=drafter,
        ))
        _workload(warm)
        warm.close()
        engine = ServingEngine(
            params, model_cfg, cfg_arm, spec_drafter=drafter,
        )
        client = ServingClient(engine)
        sentinel = RecompileSentinel(
            budget=(None if args.allow_recompiles < 0
                    else args.allow_recompiles),
            name=f"serve-bench-spec-{'on' if spec_on else 'off'}-window",
        )
        with sentinel:
            wall, out_tokens, toks = _workload(client)
        stats = engine.spec_stats() if spec_on else None
        client.close()
        return wall, out_tokens, toks, sentinel.count, stats

    base_wall, base_tokens, base_toks, base_compiles, _ = _arm(False)
    spec_wall, spec_tokens, spec_toks, spec_compiles, spec_stats = (
        _arm(True)
    )
    match_rate = None
    if args.temperature <= 0:
        total = sum(len(t) for t in base_toks.values())
        agree = sum(
            1
            for i, t in base_toks.items()
            for a, b in zip(t, spec_toks.get(i, []))
            if a == b
        )
        match_rate = agree / max(1, total)
        if args.spec_verify == "exact":
            # the exact verify mode is bit-identical BY CONSTRUCTION;
            # batched mode only reports the rate (greedy near-ties may
            # resolve differently at large contractions)
            assert base_toks == spec_toks, (
                "greedy spec output diverged from the non-spec "
                "baseline under spec_verify=exact"
            )
    base_tps = base_tokens / base_wall
    spec_tps = spec_tokens / spec_wall
    line = {
        "metric": "serving_spec_output_tokens_per_sec",
        "value": round(spec_tps, 1),
        "unit": "tokens/sec",
        "spec_tok_per_s": round(spec_tps, 1),
        "baseline_tok_per_s": round(base_tps, 1),
        "spec_speedup": round(spec_tps / base_tps, 3) if base_tps else None,
        "spec_acceptance_rate": (
            spec_stats["acceptance_rate"] if spec_stats else None
        ),
        "spec_proposed": spec_stats["proposed"] if spec_stats else 0,
        "spec_accepted": spec_stats["accepted"] if spec_stats else 0,
        "spec_mode": args.spec,
        "spec_verify": args.spec_verify,
        "spec_draft_len": args.spec_draft_len,
        "spec_drafter_ckpt": args.spec_drafter_ckpt,
        "compiles_in_window": spec_compiles,
        "baseline_compiles_in_window": base_compiles,
        "greedy_token_match_rate": (
            None if match_rate is None else round(match_rate, 5)
        ),
        "n_requests": len(prompts),
        "output_tokens": spec_tokens,
        "wall_s": round(spec_wall, 3),
        "model": model_cfg.model,
        "decode_attention_impl": (
            serving.decode_attention_impl
            or model_cfg.decode_attention_impl
        ),
        "kv_cache_dtype": kv_store_dtype(
            model_cfg if not serving.kv_cache_dtype
            else model_cfg.replace(kv_cache_dtype=serving.kv_cache_dtype)
        ),
        "kv_page_size": serving.kv_page_size,
        "num_slots": serving.num_slots,
        "clients": args.clients,
        "new_tokens": args.new_tokens,
        "temperature": args.temperature,
        "prompt_len_range": [min_prompt, max_prompt],
        "smoke": bool(args.smoke),
    }
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] spec A/B ({args.spec}, k={args.spec_draft_len}) "
        f"baseline={base_tps:.1f} tok/s spec={spec_tps:.1f} tok/s "
        f"speedup={line['spec_speedup']}x "
        f"acceptance={line['spec_acceptance_rate']} "
        f"compiles={spec_compiles}",
        file=sys.stderr,
    )


def _run_quality_ab(args, params, model_cfg, serving) -> None:
    """``--quality-ab`` workload: the SAME closed-loop load run twice —
    telemetry OFF, then telemetry ON — against fresh engines at
    otherwise identical config, reported as one JSON line. Measures
    the acceptance criterion directly: ``quality_overhead_pct`` (the
    tok/s cost of the in-step quality tail; budget < 3% on smoke) and
    ``compiles_in_window`` (the quality arm's zero-recompile pin).
    Greedy traffic keeps the on arm bit-identical to the off arm —
    asserted token-for-token: the telemetry columns are APPENDED to
    the packed step outputs, never read by the sampling path."""
    import jax  # noqa: F401  (engine stack below pulls it in anyway)

    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )
    from differential_transformer_replication_tpu.serving import (
        ServingClient,
        ServingEngine,
    )

    rng = np.random.default_rng(args.seed)
    max_prompt = min(args.max_prompt,
                     model_cfg.block_size - args.new_tokens - 1)
    min_prompt = max(1, min(args.min_prompt, max_prompt))
    prompts = [
        rng.integers(
            0, model_cfg.vocab_size,
            size=int(rng.integers(min_prompt, max_prompt + 1)),
        ).tolist()
        for _ in range(args.requests)
    ]

    def _workload(client):
        return spec_workload(client, prompts, args.new_tokens,
                             args.clients, args.seed, args.temperature)

    def _mk_arm(quality_on):
        cfg_arm = serving.replace(
            quality_telemetry=quality_on,
            quality_fingerprint=(args.quality_fingerprint or ""
                                 if quality_on else ""),
        )
        warm = ServingClient(ServingEngine(params, model_cfg, cfg_arm))
        _workload(warm)
        warm.close()
        engine = ServingEngine(params, model_cfg, cfg_arm)
        client = ServingClient(engine)
        sentinel = RecompileSentinel(
            budget=(None if args.allow_recompiles < 0
                    else args.allow_recompiles),
            name=f"serve-bench-quality-{'on' if quality_on else 'off'}"
                 "-window",
        )
        return engine, client, sentinel

    # the two arms ALTERNATE timed passes (best-of-3 per arm): the
    # comparison is percent-level, so a background-load burst during
    # one sequential arm would swing the verdict by tens of percent —
    # alternating lands any burst on both arms, and the per-arm best
    # pass is the least-disturbed measurement of each
    arms = {q: _mk_arm(q) for q in (False, True)}
    best = {False: None, True: None}
    first_toks = {}
    compiles = {False: 0, True: 0}
    for _ in range(3):
        for quality_on in (False, True):
            _, client, sentinel = arms[quality_on]
            with sentinel:
                wall, out_tokens, toks = _workload(client)
            compiles[quality_on] = max(compiles[quality_on],
                                       sentinel.count)
            first_toks.setdefault(quality_on, toks)
            if best[quality_on] is None or wall < best[quality_on][0]:
                best[quality_on] = (wall, out_tokens)
    on_engine = arms[True][0]
    q_stats = on_engine.quality_stats()
    if args.quality_record:
        from differential_transformer_replication_tpu.obs.quality import (
            save_fingerprint,
        )

        save_fingerprint(
            args.quality_record,
            on_engine.quality_fingerprint(
                meta={"model": model_cfg.model, "bench": "serve_bench"}
            ),
        )
    for _, client, _ in arms.values():
        client.close()
    off_wall, off_tokens = best[False]
    on_wall, on_tokens = best[True]
    off_toks, on_toks = first_toks[False], first_toks[True]
    off_compiles, on_compiles = compiles[False], compiles[True]
    if args.temperature <= 0:
        # telemetry must be a pure OBSERVER: greedy outputs bit-match
        assert off_toks == on_toks, (
            "greedy output diverged with quality telemetry on — the "
            "telemetry tail is supposed to observe, not perturb"
        )
    off_tps = off_tokens / off_wall
    on_tps = on_tokens / on_wall
    line = {
        "metric": "serving_quality_overhead_pct",
        "value": round((1.0 - on_tps / off_tps) * 100.0, 2)
        if off_tps else None,
        "unit": "percent",
        "quality_tok_per_s": round(on_tps, 1),
        "baseline_tok_per_s": round(off_tps, 1),
        "quality_overhead_pct": round((1.0 - on_tps / off_tps) * 100.0,
                                      2) if off_tps else None,
        "quality": q_stats,
        "compiles_in_window": on_compiles,
        "baseline_compiles_in_window": off_compiles,
        "greedy_bit_identical": args.temperature <= 0,
        "quality_fingerprint": args.quality_fingerprint,
        "quality_record": args.quality_record,
        "n_requests": len(prompts),
        "output_tokens": on_tokens,
        "wall_s": round(on_wall, 3),
        "model": model_cfg.model,
        "num_slots": serving.num_slots,
        "clients": args.clients,
        "new_tokens": args.new_tokens,
        "temperature": args.temperature,
        "prompt_len_range": [min_prompt, max_prompt],
        "smoke": bool(args.smoke),
    }
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] quality A/B off={off_tps:.1f} tok/s "
        f"on={on_tps:.1f} tok/s "
        f"overhead={line['quality_overhead_pct']}% "
        f"drift={q_stats.get('drift') if q_stats else None} "
        f"compiles={on_compiles}",
        file=sys.stderr,
    )


_CONSTRAINT_SPECS = {
    # every canned spec is BOUNDED (no unbounded repetition), so each
    # constrained request reaches an accepting terminal state well
    # inside its token budget and schema_validity_rate can hit 1.0
    "json": {"json_schema": json.dumps({
        "type": "object",
        "properties": {"ok": {"type": "boolean"}},
        "required": ["ok"],
    })},
    "regex": {"regex": "[ab]{4,8}"},
    "choices": {"choices": ("yes", "no", "maybe")},
}


def _run_constrained_ab(args, params, model_cfg, serving) -> None:
    """``--constrained SPEC`` workload: ONE engine, MIXED traffic —
    alternating constrained (FSM-masked, serving/constrain.py) and
    unconstrained requests through the same jitted pool step — measured
    under the RecompileSentinel. Constraints ride runtime arrays, so
    ``compiles_in_window`` must stay 0: mixed traffic is the whole
    point of the design. Every constrained output is re-walked through
    an independently compiled FSM (``schema_validity_rate``); the
    canned specs are bounded, so 1.0 is the only acceptable value.
    Compose with ``--spec ngram`` for the constrained+speculative arm
    (drafts are FSM-pre-truncated, then verify re-checks)."""
    import jax  # noqa: F401  (engine stack below pulls it in anyway)

    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )
    from differential_transformer_replication_tpu.models.decode import (
        kv_store_dtype,
    )
    from differential_transformer_replication_tpu.serving import (
        SamplingParams,
        ServingClient,
        ServingEngine,
    )
    from differential_transformer_replication_tpu.serving.constrain import (
        compile_constraint,
        spec_key,
    )

    ckw = _CONSTRAINT_SPECS[args.constrained]
    # synthetic char vocab: id -> its ASCII char, the idiom the real
    # server gets from data/tokenizer.vocab_strings. Ids outside
    # printable ASCII decode to "" (never allowed under a constraint;
    # unconstrained requests still sample them freely)
    vocab = [
        chr(i) if 32 <= i < 127 else "" for i in range(model_cfg.vocab_size)
    ]
    if args.spec:
        serving = serving.replace(
            spec_mode=args.spec, spec_draft_len=args.spec_draft_len,
            spec_verify=args.spec_verify,
        )
        if args.spec == "model":
            raise SystemExit(
                "--constrained composes with --spec ngram (the model "
                "drafter would need a checkpoint sharing this synthetic "
                "char vocab)"
            )

    rng = np.random.default_rng(args.seed)
    max_prompt = min(args.max_prompt,
                     model_cfg.block_size - args.new_tokens - 1)
    min_prompt = max(1, min(args.min_prompt, max_prompt))
    prompts = [
        rng.integers(
            0, model_cfg.vocab_size,
            size=int(rng.integers(min_prompt, max_prompt + 1)),
        ).tolist()
        for _ in range(args.requests)
    ]
    constrained_ids = set(range(0, len(prompts), 2))  # even = constrained

    def _params(i):
        kw = dict(ckw) if i in constrained_ids else {}
        return SamplingParams(
            max_new_tokens=args.new_tokens,
            temperature=args.temperature, seed=args.seed + i, **kw,
        )

    def _workload(client):
        completed = {}
        lock = threading.Lock()
        next_idx = [0]

        def worker():
            while True:
                with lock:
                    i = next_idx[0]
                    if i >= len(prompts):
                        return
                    next_idx[0] += 1
                out = client.generate(prompts[i], params=_params(i),
                                      timeout=600)
                with lock:
                    completed[i] = out

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker)
                   for _ in range(args.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert len(completed) == len(prompts), "requests went missing"
        return wall, completed

    # unmeasured warm pass (fresh engine; the jitted closures are
    # module-cached, so its compiles carry to the measured engine)
    warm = ServingClient(ServingEngine(params, model_cfg, serving,
                                       vocab=vocab))
    _workload(warm)
    warm.close()
    engine = ServingEngine(params, model_cfg, serving, vocab=vocab)
    client = ServingClient(engine)
    sentinel = RecompileSentinel(
        budget=(None if args.allow_recompiles < 0
                else args.allow_recompiles),
        name="serve-bench-constrained-window",
    )
    with sentinel:
        wall, completed = _workload(client)
    spec_stats = engine.spec_stats() if args.spec else None
    cstats = engine.constrain_stats()
    client.close()

    # validity oracle: an FSM compiled OUTSIDE the engine re-walks
    # every constrained output end to end
    sp0 = _params(0)
    fsm = compile_constraint(spec_key(sp0, serving.eos_token_id), vocab)
    eos = serving.eos_token_id
    n_valid = 0
    finish_reasons = {}
    c_tokens = u_tokens = 0
    for i, out in completed.items():
        if i not in constrained_ids:
            u_tokens += len(out.tokens)
            continue
        c_tokens += len(out.tokens)
        toks = list(out.tokens)
        if eos is not None and toks and toks[-1] == eos:
            toks.pop()
        if fsm.matches(toks):
            n_valid += 1
        fr = out.finish_reason
        finish_reasons[fr] = finish_reasons.get(fr, 0) + 1
    n_con = len(constrained_ids)
    validity = n_valid / max(1, n_con)
    con_tps = c_tokens / wall
    unc_tps = u_tokens / wall
    line = {
        "metric": "serving_constrained_output_tokens_per_sec",
        "value": round(con_tps, 1),
        "unit": "tokens/sec",
        "constrained_spec": args.constrained,
        "schema_validity_rate": round(validity, 5),
        "constrained_tok_per_s": round(con_tps, 1),
        "unconstrained_tok_per_s": round(unc_tps, 1),
        "compiles_in_window": sentinel.count,
        "constraint_cache": {
            k: cstats[k]
            for k in ("entries", "bytes", "hits_total", "misses_total")
        },
        "constrained_finish_reasons": finish_reasons,
        "n_constrained": n_con,
        "n_unconstrained": len(prompts) - n_con,
        "spec_mode": args.spec or "",
        "spec_acceptance_rate": (
            spec_stats["acceptance_rate"] if spec_stats else None
        ),
        "output_tokens": c_tokens + u_tokens,
        "wall_s": round(wall, 3),
        "model": model_cfg.model,
        "decode_attention_impl": (
            serving.decode_attention_impl
            or model_cfg.decode_attention_impl
        ),
        "kv_cache_dtype": kv_store_dtype(
            model_cfg if not serving.kv_cache_dtype
            else model_cfg.replace(kv_cache_dtype=serving.kv_cache_dtype)
        ),
        "kv_page_size": serving.kv_page_size,
        "num_slots": serving.num_slots,
        "clients": args.clients,
        "new_tokens": args.new_tokens,
        "temperature": args.temperature,
        "prompt_len_range": [min_prompt, max_prompt],
        "smoke": bool(args.smoke),
    }
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] constrained A/B ({args.constrained}"
        f"{'+spec ' + args.spec if args.spec else ''}) "
        f"validity={validity:.3f} constrained={con_tps:.1f} tok/s "
        f"unconstrained={unc_tps:.1f} tok/s "
        f"compiles={sentinel.count} "
        f"cache_hits={cstats['hits_total']}",
        file=sys.stderr,
    )


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny model + few requests; seconds on CPU")
    p.add_argument("--checkpoint", default=None,
                   help="serve a trained checkpoint instead of random init")
    p.add_argument("--model", default="diff",
                   choices=("control", "diff", "ndiff"))
    p.add_argument("--n-layer", type=int, default=8)
    p.add_argument("--n-embd", type=int, default=768)
    p.add_argument("--n-head", type=int, default=4)
    p.add_argument("--block-size", type=int, default=512)
    p.add_argument("--vocab-size", type=int, default=12000)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--clients", type=int, default=16,
                   help="closed-loop concurrency")
    p.add_argument("--num-slots", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=128)
    p.add_argument("--prefill-budget", type=int, default=256)
    p.add_argument("--decode-attention-impl", default="",
                   choices=("", "xla", "pallas"),
                   help="serving-side override of the decode attention "
                        "backend (ops/decode_attention.py fused kernel "
                        "vs plain XLA); '' inherits the model config")
    p.add_argument("--kv-cache-dtype", default="",
                   choices=("", "auto", "bf16", "int8"),
                   help="KV-cache storage dtype override: int8 stores "
                        "per-head-scale quantized K/V (~half the bf16 "
                        "bytes per slot); '' inherits the model config")
    p.add_argument("--shared-prefix", default=None, metavar="N:M",
                   help="shared-prefix workload against the paged "
                        "radix cache: N sessions sharing an M-token "
                        "system prompt, run as a cold 'miss' phase "
                        "(unique prefixes) then a primed 'hit' phase "
                        "(shared prefix); the JSON line reports TTFT "
                        "split by cache-hit/miss and "
                        "prefix_cache_hit_rate. In-process only; "
                        "implies --kv-page-size 16 when unset")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="paged KV cache (serving/pages.py): tokens per "
                        "page (must divide block size); 0 = contiguous "
                        "per-slot rings")
    p.add_argument("--kv-pool-pages", type=int, default=0,
                   help="total physical pages in the paged pool; 0 = "
                        "auto (num_slots * block_size / page_size). "
                        "Size below auto to bench MORE slots at equal "
                        "HBM (admission keys on free pages)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the radix shared-prefix cache")
    p.add_argument("--prefix-cache-pages", type=int, default=0,
                   help="extra pool pages kept as cached-prefix "
                        "headroom")
    p.add_argument("--constrained", default=None,
                   choices=tuple(sorted(_CONSTRAINT_SPECS)),
                   help="structured-decoding A/B (serving/constrain.py): "
                        "mixed traffic — alternating constrained and "
                        "unconstrained requests — through ONE engine, "
                        "measured under the RecompileSentinel. The JSON "
                        "line reports schema_validity_rate (every "
                        "constrained output re-walked through an "
                        "independently compiled FSM; must be 1.0), "
                        "constrained_tok_per_s vs "
                        "unconstrained_tok_per_s, compiles_in_window "
                        "(must be 0: constraints ride runtime arrays) "
                        "and constraint-cache hit counters. Canned "
                        "specs over a synthetic ASCII char vocab: "
                        "'json' (a boolean-field object schema), "
                        "'regex' ([ab]{4,8}), 'choices' (yes/no/"
                        "maybe). Composes with --spec ngram for the "
                        "constrained+speculative arm. In-process only")
    p.add_argument("--spec", default=None, choices=("ngram", "model"),
                   help="speculative-decoding A/B (serving/spec.py): "
                        "run the SAME workload twice — non-spec "
                        "baseline, then with this drafter — and "
                        "report spec_acceptance_rate, spec_tok_per_s "
                        "vs baseline_tok_per_s, spec_speedup, and "
                        "compiles_in_window for the spec path, all in "
                        "the one JSON line. In-process only. The "
                        "n-gram drafter pays off on repetitive "
                        "decoding (greedy --temperature 0); the model "
                        "drafter wants a trained --spec-drafter-ckpt "
                        "sharing the target's tokenizer")
    p.add_argument("--spec-draft-len", type=int, default=4,
                   help="draft tokens verified per slot per iteration "
                        "(the compiled k rung)")
    p.add_argument("--spec-drafter-ckpt", default=None,
                   help="drafter checkpoint for --spec model, or the "
                        "literal 'self' to draft with the target's "
                        "own params (the acceptance~1 upper bound of "
                        "the verify machinery)")
    p.add_argument("--spec-verify", default="exact",
                   choices=("exact", "batched"),
                   help="verify-step formulation: 'exact' unrolls k+1 "
                        "engine-native sub-steps (greedy bit-identical "
                        "to non-spec at any size — asserted); "
                        "'batched' streams each slot's KV once for "
                        "all rows through the fused multi-query "
                        "kernel (the TPU-bandwidth formulation; "
                        "greedy ties may resolve differently at "
                        "large sizes, so the A/B reports "
                        "greedy_token_match_rate instead of "
                        "asserting)")
    p.add_argument("--quality", action="store_true",
                   help="run the in-process engine with model-quality "
                        "telemetry (obs/quality.py): the JSON line "
                        "gains a 'quality' block — mean token entropy "
                        "/ logit margin, drift vs --quality-"
                        "fingerprint, constraint validity")
    p.add_argument("--quality-ab", action="store_true",
                   help="quality-telemetry A/B: the SAME closed-loop "
                        "load with telemetry off then on, against "
                        "fresh engines; reports quality_overhead_pct "
                        "(the in-step telemetry tail's tok/s cost), "
                        "per-arm compiles_in_window, and asserts "
                        "greedy bit-parity between arms. In-process "
                        "only")
    p.add_argument("--quality-fingerprint", default=None,
                   help="reference quality fingerprint JSON to score "
                        "live drift against (recorded earlier with "
                        "--quality-record)")
    p.add_argument("--quality-record", default=None,
                   help="write the run's quality fingerprint to this "
                        "path after the measured window (implies "
                        "--quality)")
    p.add_argument("--priority-mix", default=None, metavar="CLS:N,...",
                   help="priority-class workload mix, e.g. "
                        "'high:8,batch:56': run exactly N requests of "
                        "each named class (high/normal/batch), "
                        "deterministically interleaved; overrides "
                        "--requests with the mix total. The JSON line "
                        "gains per-class TTFT/ITL percentiles "
                        "(ttft_ms_by_class / itl_ms_by_class) so the "
                        "priority scheduler's isolation under load is "
                        "measurable. In-process / local --http only")
    p.add_argument("--working-set-mult", type=float, default=0.0,
                   help="graceful-degradation bench: size the prefix "
                        "working set to K x the physical page pool — "
                        "requests cycle through enough distinct page-"
                        "aligned prefixes that the radix cache MUST "
                        "evict, so revisits can only hit via host-RAM "
                        "demote/promote (--host-tier-bytes). The JSON "
                        "line gains host_tier_hit_rate and the "
                        "demote/promote/preempt counters. Implies "
                        "--kv-page-size 16 when unset; in-process "
                        "only; 0 = off")
    p.add_argument("--host-tier-bytes", type=int, default=0,
                   help="host-RAM page-tier byte budget (ServingConfig."
                        "host_tier_bytes): radix pages evicted under "
                        "pool pressure demote to pinned host buffers "
                        "and promote back by copy on a later "
                        "admission instead of recomputing; 0 = off")
    p.add_argument("--min-prompt", type=int, default=16)
    p.add_argument("--max-prompt", type=int, default=128)
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--http", action="store_true",
                   help="drive the load through the stdlib HTTP server "
                        "(ephemeral port) instead of in-process calls")
    p.add_argument("--target", action="append", default=None,
                   help="base URL of an ALREADY-RUNNING server or "
                        "router (repeat for several replicas, round-"
                        "robin); implies --http, skips the local "
                        "engine entirely")
    p.add_argument("--max-retries", type=int, default=3,
                   help="per-request retry budget for retriable "
                        "failures (503 / engine crash)")
    p.add_argument("--allow-recompiles", type=int, default=0,
                   help="XLA compile budget for the measured window "
                        "(in-process modes). Warmup compiles every "
                        "shape this load can produce, so the default 0 "
                        "makes a silent recompile FAIL the bench "
                        "(analysis/sanitizers.py RecompileSentinel) "
                        "instead of quietly degrading tok/s; -1 "
                        "disables the pin")
    p.add_argument("--max-queue-len", type=int, default=0,
                   help="engine admission bound; 0 = unbounded")
    p.add_argument("--deadline", type=float, default=0.0,
                   help="server-side per-request deadline in seconds; "
                        "0 = none")
    p.add_argument("--drain-during-run", default=None, metavar="URL",
                   help="HTTP mode, router target only: mid-run, POST "
                        "the router's /drain for this replica URL (live "
                        "migration of its in-flight decodes to peers). "
                        "The JSON line gains a 'drain' block "
                        "(drain_seconds + migrated/finished/failed "
                        "counts) plus per-request migrated/replayed "
                        "tallies — the zero-loss-failover bench arm")
    p.add_argument("--drain-delay-s", type=float, default=1.0,
                   help="seconds into the measured window before the "
                        "--drain-during-run POST fires")
    p.add_argument("--trace", default=None,
                   help="open-loop load-trace replay against --target: "
                        "a JSONL file of {\"t\": seconds} arrival rows, "
                        "or diurnal:DURATION:LOW:HIGH to synthesize one "
                        "cosine day (reports per-window req/s, shed "
                        "rate, TTFT burn, and the replica-hours "
                        "integral from the router's /health)")
    p.add_argument("--trace-window", type=float, default=5.0,
                   help="trace-replay reporting window in seconds")
    p.add_argument("--ttft-slo", type=float, default=1.0,
                   help="trace-replay TTFT objective bound in seconds")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="trace-replay fraction of requests that must "
                        "be good (served AND under --ttft-slo)")
    p.add_argument("--out", default=None,
                   help="also append the JSON line to this file")
    p.add_argument("--profile-every", type=int, default=0,
                   help="continuous on-device profiling of the "
                        "in-process engine (obs/device_profile.py): "
                        "capture every Nth engine iteration's device "
                        "profile into <--trace-dir or a temp dir>/"
                        "device_profiles (device_* gauges, "
                        "device_profile JSONL rows, stitchable "
                        "device-lane traces); 0 = off")
    p.add_argument("--trace-dir", default=None,
                   help="directory for span traces: the in-process "
                        "engine writes <dir>/serve_bench.engine."
                        "trace.json; in --target mode this names where "
                        "the fleet's own --trace-path files live, and "
                        "rides into the JSON line so the slow_exemplars "
                        "trace ids can be stitched (tools/"
                        "trace_stitch.py) without guessing paths")
    args = p.parse_args()

    if args.smoke:
        args.model = "control"
        args.n_layer, args.n_embd, args.n_head = 2, 32, 2
        args.block_size, args.vocab_size = 32, 97
        args.requests, args.clients, args.num_slots = 8, 4, 4
        args.prefill_chunk, args.prefill_budget = 8, 16
        args.min_prompt, args.max_prompt, args.new_tokens = 3, 12, 8
        if args.shared_prefix:
            # smoke geometry: page smaller than the shared prefix so
            # the hit phase actually skips pages (3-token tails leave
            # room inside block_size=32)
            args.max_prompt, args.new_tokens = 4, 6
            if args.kv_page_size == 0:
                args.kv_page_size = 8
        if args.spec:
            # spec smoke: greedy + a longer tail so the repetitive
            # stretches the n-gram drafter feeds on actually develop,
            # and short prompts so drafts stay in-window
            args.block_size = 64
            args.requests, args.clients = 8, 4
            args.max_prompt, args.new_tokens = 10, 24
            args.temperature = 0.0
        if args.quality_ab:
            # quality smoke: the A/B measures a per-token overhead, so
            # the timed window must be long enough that one scheduler
            # hiccup can't swamp it (the default 64-token smoke window
            # is ~40 ms — pure noise for a percent-level comparison)
            args.requests, args.new_tokens = 64, 24
        if args.constrained:
            # constrained smoke: the char vocab must cover printable
            # ASCII (the JSON spec needs '{' = 0x7b), and the token
            # budget must cover the longest bounded path of every
            # canned spec ('{"ok":false}' = 13 single-char tokens)
            args.vocab_size = 128
            args.new_tokens = max(args.new_tokens, 16)
            args.block_size = max(args.block_size,
                                  args.max_prompt + args.new_tokens + 4)
    if args.constrained and (args.target or args.http):
        raise SystemExit(
            "--constrained is an in-process A/B bench (it builds the "
            "engine with a synthetic char vocab and reads the "
            "constraint-cache counters directly)"
        )
    if args.spec and (args.target or args.http):
        raise SystemExit(
            "--spec is an in-process A/B bench (it builds both engines "
            "and reads the acceptance counters directly)"
        )
    if args.shared_prefix:
        if args.target or args.http:
            raise SystemExit(
                "--shared-prefix is an in-process engine bench "
                "(it reads the page pool's hit counters directly)"
            )
        if args.kv_page_size == 0:
            args.kv_page_size = 16
    if args.priority_mix and args.target:
        raise SystemExit(
            "--priority-mix drives the in-process engine (per-class "
            "latency needs the engine's own attribution, not a remote "
            "fleet's)"
        )
    if args.quality_record:
        args.quality = True
    if (args.quality or args.quality_ab) and args.target:
        raise SystemExit(
            "--quality/--quality-ab drive the in-process engine "
            "(they read engine.quality_stats() directly; against a "
            "fleet use --quality-telemetry on the servers and "
            "tools/slo_report.py)"
        )
    if args.quality_ab and args.http:
        raise SystemExit(
            "--quality-ab is an in-process A/B bench (it builds both "
            "engines and compares their outputs token-for-token)"
        )
    if args.working_set_mult:
        if args.target or args.http:
            raise SystemExit(
                "--working-set-mult is an in-process paged-engine "
                "bench (it sizes the working set off the pool and "
                "reads the host-tier counters directly)"
            )
        if args.kv_page_size == 0:
            args.kv_page_size = 16

    # retry helpers are stdlib-only (serving/retry.py); the engine
    # stack — and jax — loads only when the load runs in-process
    from differential_transformer_replication_tpu.serving.retry import (
        call_with_retries,
        http_post_json_with_retries,
    )

    targets = [
        t if t.endswith("/generate") else t.rstrip("/") + "/generate"
        for t in (args.target or [])
    ]
    if args.trace:
        if not targets:
            raise SystemExit(
                "--trace needs --target (replay drives a live "
                "fleet/router over HTTP)"
            )
        args.http = True
        _run_trace_replay(args, targets, http_post_json_with_retries)
        return
    if targets:
        args.http = True
        _run_against_targets(args, targets,
                             http_post_json_with_retries)
        return

    import jax

    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    # the load runs in-process from here on: it times THIS device
    start_measurement("serve_bench", smoke=args.smoke)
    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        ServingConfig,
    )
    from differential_transformer_replication_tpu.models.decode import (
        kv_store_dtype,
    )
    from differential_transformer_replication_tpu.serving import (
        DeadlineExceededError,
        EngineCrashError,
        QueueFullError,
        ServingClient,
        ServingEngine,
        ShuttingDownError,
        serve,
    )

    if args.checkpoint:
        from differential_transformer_replication_tpu.train.checkpoint import (
            load_params_for_inference,
        )

        params, model_cfg, _ = load_params_for_inference(args.checkpoint)
    else:
        from differential_transformer_replication_tpu.models import (
            init_model,
        )

        model_cfg = ModelConfig(
            model=args.model, vocab_size=args.vocab_size,
            n_embd=args.n_embd, n_head=args.n_head, n_layer=args.n_layer,
            block_size=args.block_size, dropout=0.0,
            compute_dtype="float32" if args.smoke else "bfloat16",
        )
        params = init_model(jax.random.PRNGKey(args.seed), model_cfg)

    profile_dir = None
    if args.profile_every > 0:
        profile_dir = os.path.join(
            args.trace_dir or tempfile.mkdtemp(prefix="serve_bench_"),
            "device_profiles",
        )
    serving = ServingConfig(
        num_slots=args.num_slots, prefill_chunk=args.prefill_chunk,
        prefill_budget=args.prefill_budget,
        max_queue_len=args.max_queue_len,
        default_deadline_s=args.deadline,
        decode_attention_impl=args.decode_attention_impl,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        prefix_cache=not args.no_prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        host_tier_bytes=args.host_tier_bytes,
        profile_every=args.profile_every,
        profile_dir=profile_dir or "device_profiles",
        # let RoPE families roll past block_size so a full-window prompt
        # plus new_tokens always fits (the diff family ignores this and
        # stays hard-capped at block_size)
        max_seq_len=model_cfg.block_size + args.new_tokens,
        quality_telemetry=bool(args.quality),
        quality_fingerprint=args.quality_fingerprint or "",
    )
    tracer = None
    if args.trace_dir:
        from differential_transformer_replication_tpu.obs.spans import (
            SpanTracer,
        )

        os.makedirs(args.trace_dir, exist_ok=True)
        tracer = SpanTracer(
            os.path.join(args.trace_dir, "serve_bench.engine.trace.json"),
            process_name="serve-bench-engine",
        )
    if args.constrained:
        # handles --spec itself (the constrained+speculative arm)
        _run_constrained_ab(args, params, model_cfg, serving)
        return
    if args.spec:
        _run_spec_ab(args, params, model_cfg, serving)
        return
    if args.quality_ab:
        _run_quality_ab(args, params, model_cfg, serving)
        return

    engine = ServingEngine(params, model_cfg, serving, tracer=tracer)
    client = ServingClient(engine)

    if args.shared_prefix:
        _run_shared_prefix(args, client, engine, serving, model_cfg,
                           tracer)
        return

    httpd = None
    url = None
    if args.http:
        httpd = serve(client, port=0)  # ephemeral port
        url = f"http://127.0.0.1:{httpd.server_address[1]}/generate"
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

    rng = np.random.default_rng(args.seed)
    max_prompt = min(
        args.max_prompt, model_cfg.block_size - args.new_tokens
        if model_cfg.model == "diff" else model_cfg.block_size
    )
    min_prompt = min(args.min_prompt, max_prompt)
    mix = _parse_priority_mix(args.priority_mix)
    priorities = None
    if mix:
        args.requests = sum(mix.values())
        labels = [c for c, n in sorted(mix.items()) for _ in range(n)]
        # deterministic interleave: every class arrives throughout the
        # run (all-high-then-all-batch would never contend)
        priorities = [labels[k] for k in rng.permutation(len(labels))]
    V = model_cfg.vocab_size
    ws_prefixes = 0
    if args.working_set_mult > 0:
        # K x the pool in distinct page-aligned prefixes, revisited
        # round-robin: by the time a prefix comes around again the
        # radix cache has evicted it, so the revisit can only hit via
        # the host tier (or recompute when the tier is off/full)
        ps = serving.kv_page_size
        if max_prompt <= ps:
            raise SystemExit(
                f"--working-set-mult needs --max-prompt > the page "
                f"size ({ps}) so a prefix page is cacheable"
            )
        pool_pages = engine.page_stats()["total"]
        prefix_pages = max(1, (max_prompt - 1) // ps)
        prefix_len = prefix_pages * ps
        ws_prefixes = max(
            1, -(-int(args.working_set_mult * pool_pages)
                 // prefix_pages)
        )
        prefixes = [
            [j % V] + rng.integers(0, V, size=prefix_len - 1).tolist()
            for j in range(ws_prefixes)
        ]
        tail_hi = max(1, max_prompt - prefix_len)
        prompts = [
            prefixes[i % ws_prefixes]
            + rng.integers(
                0, V, size=int(rng.integers(1, tail_hi + 1)),
            ).tolist()
            for i in range(args.requests)
        ]
    else:
        prompts = [
            rng.integers(
                0, V,
                size=int(rng.integers(min_prompt, max_prompt + 1)),
            ).tolist()
            for _ in range(args.requests)
        ]

    # warmup: compile outside the timed window. Every prefill chunk any
    # request can use is a power of two <= min(prefill_chunk, max_prompt),
    # so one warm request PER ladder size (each a single-chunk prefill)
    # plus the shared decode step and samplers covers every shape — no
    # first-compile lands in a measured TTFT/ITL. Warm prompts carry
    # DISTINCT random content: with the paged radix cache on, repeated-
    # token ladders would hit the shorter entries' cached prefixes and
    # skip the longer chunk shapes they exist to compile.
    # distinct first token per ladder prompt: with the paged radix
    # cache on, a warm prompt hitting an earlier entry's cached prefix
    # would skip the chunk shape it exists to compile (every radix
    # match must match position 0 first, so this cannot collide)
    warm_rng = np.random.default_rng(args.seed + 77)
    V = model_cfg.vocab_size
    ladder, size = [], 1
    while size <= min(serving.prefill_chunk, max_prompt):
        ladder.append(size)
        size *= 2
    client.generate_batch(
        [[j % V] + warm_rng.integers(0, V, size=n - 1).tolist()
         for j, n in enumerate(ladder)],
        max_new_tokens=2, temperature=args.temperature, seed=0,
        timeout=600,
    )
    if serving.paged() and serving.prefix_cache:
        # warm the COW-fork copy too: random measured prompts can
        # partially match a cached page (first-token collision) and a
        # cold page_copy compile would land inside the sentinel window
        fork_pref = (
            [len(ladder) % V]
            + warm_rng.integers(0, V,
                                size=serving.kv_page_size).tolist()
        )
        client.generate(fork_pref + [1], max_new_tokens=2,
                        temperature=args.temperature, seed=0,
                        timeout=600)
        client.generate(fork_pref + [2, 3], max_new_tokens=2,
                        temperature=args.temperature, seed=0,
                        timeout=600)
    if serving.tiered() and max_prompt > serving.kv_page_size:
        # warm the page extract/inject jits too: overflow the pool with
        # distinct cacheable prompts until a radix eviction DEMOTES to
        # the host tier (extract), then keep overflowing and
        # periodically revisit the first warm prompt until its
        # admission PROMOTES back (inject) — a cold demote/promote
        # compile inside the sentinel window would fail the bench
        ps = serving.kv_page_size
        wlen = min(max_prompt, 2 * ps + 1)
        total = engine.page_stats()["total"]
        warm_prompts, cursor = [], 0
        for j in range(4 * total + 16):
            prompt = (
                [(len(ladder) + 1 + j) % V]
                + warm_rng.integers(0, V, size=wlen - 1).tolist()
            )
            warm_prompts.append(prompt)
            client.generate(prompt, max_new_tokens=2,
                            temperature=args.temperature, seed=0,
                            timeout=600)
            ts = engine.tier_stats() or {}
            if ts.get("promotions", 0) > 0:
                break
            if ts.get("demotions", 0) > 0 and j % 4 == 3:
                # revisit a ROLLING old prompt (a revisit re-caches its
                # target MRU, so hammering one prompt would pin it
                # on-device forever); the cursor eventually lands on a
                # prompt whose pages were evicted+demoted, and that
                # admission promotes
                client.generate(warm_prompts[cursor], max_new_tokens=2,
                                temperature=args.temperature, seed=0,
                                timeout=600)
                cursor = min(cursor + 1, len(warm_prompts) - 1)

    from differential_transformer_replication_tpu.obs import trace as trace_mod

    # one minted trace context per request (client-supplied ids, the
    # contract the router/server honor) so slow_exemplars always carry
    # a trace id, whichever mode ran
    traces = [trace_mod.mint() for _ in prompts]

    # per-request record: (output_tokens, ttft_ms, itls_ms, trace_id);
    # failures land in `errors` by type instead of vanishing
    completed = []
    errors = {"queue_full": 0, "engine_crash": 0, "deadline": 0,
              "timeout": 0, "shutting_down": 0, "other": 0}
    retries_total = [0]
    lock = threading.Lock()
    next_idx = [0]

    import random as _random

    def _record_error(exc):
        if isinstance(exc, QueueFullError):
            errors["queue_full"] += 1
        elif isinstance(exc, EngineCrashError):
            errors["engine_crash"] += 1
        elif isinstance(exc, DeadlineExceededError):
            errors["deadline"] += 1
        elif isinstance(exc, ShuttingDownError):
            errors["shutting_down"] += 1
        elif isinstance(exc, TimeoutError):
            errors["timeout"] += 1
        else:
            errors["other"] += 1

    def _record_http_503(body):
        # the server types its 503s with a machine-readable "code"
        # (serving/server.py handler) — never parse the human text
        code = (body or {}).get("code", "")
        if code == "shutting_down":
            errors["shutting_down"] += 1
        elif code in ("engine_crash", "engine_failed"):
            errors["engine_crash"] += 1
        elif code == "timeout":
            errors["timeout"] += 1
        elif code == "queue_full":
            errors["queue_full"] += 1
        else:
            errors["other"] += 1

    def worker(wid):
        rng_w = _random.Random(args.seed * 1000 + wid)
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(prompts):
                    return
                next_idx[0] += 1
            prio = priorities[i] if priorities else None
            if args.http:
                payload = {
                    "prompt_ids": prompts[i],
                    "max_new_tokens": args.new_tokens,
                    "temperature": args.temperature,
                    "seed": args.seed + i,
                    "timeout": 600,
                    "traceparent": traces[i].to_traceparent(),
                }
                if prio:
                    payload["priority"] = prio
                try:
                    status, body, retries = http_post_json_with_retries(
                        url, payload,
                        timeout=600, max_retries=args.max_retries,
                        rng=rng_w, deadline_s=args.deadline or None,
                    )
                except (OSError, ValueError) as e:
                    # transport dead (or garbage body) past retry budget
                    with lock:
                        errors["other"] += 1
                        retries_total[0] += getattr(
                            e, "retry_attempts", 0)
                    continue
                with lock:
                    retries_total[0] += retries
                    if status == 200:
                        # the HTTP payload carries TTFT but not the
                        # per-token timestamps ITL needs
                        completed.append(
                            (len(body["tokens"]), body["ttft_ms"], [],
                             body.get("trace_id"), prio)
                        )
                    elif status == 503:
                        _record_http_503(body)
                    elif status == 504:
                        errors["deadline"] += 1
                    else:
                        errors["other"] += 1
            else:
                kw = {"priority": prio} if prio else {}
                try:
                    out, retries = call_with_retries(
                        lambda: client.generate(
                            prompts[i], max_new_tokens=args.new_tokens,
                            temperature=args.temperature,
                            seed=args.seed + i, timeout=600,
                            trace=traces[i], **kw,
                        ),
                        max_retries=args.max_retries,
                        retriable=(QueueFullError, EngineCrashError),
                        rng=rng_w,
                    )
                except Exception as e:
                    with lock:
                        _record_error(e)
                        # attempts burned by an ultimately-failed
                        # request still count as retries
                        retries_total[0] += getattr(
                            e, "retry_attempts", 0)
                    continue
                with lock:
                    retries_total[0] += retries
                    completed.append((
                        len(out.tokens), out.ttft * 1e3,
                        [itl * 1e3 for itl in out.itls],
                        out.trace_id, prio,
                    ))

    # the measured window is pinned recompile-free: warmup above
    # compiled the whole prefill ladder + decode + samplers, so any
    # compilation here means latencies silently include XLA compile
    # time — fail the bench loudly rather than report degraded numbers
    from differential_transformer_replication_tpu.analysis.sanitizers import (
        RecompileSentinel,
    )

    sentinel = RecompileSentinel(
        budget=None if args.allow_recompiles < 0 else args.allow_recompiles,
        name="serve-bench-measured-window",
    )
    tier0 = engine.tier_stats()
    with sentinel:
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker, args=(w,))
            for w in range(args.clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    tier1 = engine.tier_stats()
    if httpd is not None:
        httpd.shutdown()
        httpd.server_close()
    client.close()

    if tracer is not None:
        tracer.close()
    out_tokens = sum(e[0] for e in completed)
    ttfts_ms = [e[1] for e in completed]
    itls_ms = [itl for e in completed for itl in e[2]]
    n_failed = sum(errors.values())
    line = {
        "metric": "serving_output_tokens_per_sec",
        "value": round(out_tokens / wall, 1),
        "unit": "tokens/sec",
        "requests_per_sec": round(len(completed) / wall, 3),
        "ttft_ms": _percentiles(ttfts_ms),
        "itl_ms": _percentiles(itls_ms),
        "n_requests": len(completed),
        "errors": errors,
        "retries": retries_total[0],
        "failed": n_failed,
        "output_tokens": out_tokens,
        "wall_s": round(wall, 3),
        "slow_exemplars": _slow_exemplars(completed),
        "trace_dir": args.trace_dir,
        "compiles_in_window": sentinel.count,
        "preemptions": tier1["preemptions"] if tier1 else 0,
        "resumes": tier1["resumes"] if tier1 else 0,
        # continuous-profiling summary (when --profile-every sampled
        # this run): parsed capture count + where the device lanes and
        # device_profile JSONL rows landed
        "device_profile_captures": (
            engine._device_prof.captures
            if engine._device_prof is not None else 0
        ),
        "device_profile_dir": profile_dir,
        "model": model_cfg.model,
        # resolved from the ENGINE's config (serving-side overrides
        # applied) so the JSON names what actually ran
        "decode_attention_impl": engine.cfg.decode_attention_impl,
        "kv_cache_dtype": kv_store_dtype(engine.cfg),
        "kv_page_size": serving.kv_page_size,
        "num_slots": serving.num_slots,
        "clients": args.clients,
        "prefill_chunk": serving.prefill_chunk,
        "prefill_budget": serving.prefill_budget,
        "new_tokens": args.new_tokens,
        "prompt_len_range": [min_prompt, max_prompt],
        "http": bool(args.http),
        "smoke": bool(args.smoke),
    }
    if priorities:
        by_ttft: dict = {}
        by_itl: dict = {}
        for e in completed:
            by_ttft.setdefault(e[4], []).append(e[1])
            by_itl.setdefault(e[4], []).extend(e[2])
        line["priority_mix"] = mix
        line["ttft_ms_by_class"] = {
            c: _percentiles(v) for c, v in sorted(by_ttft.items())
        }
        line["itl_ms_by_class"] = {
            c: _percentiles(v) for c, v in sorted(by_itl.items())
        }
    if tier1 is not None:
        # hit rate over the MEASURED window only (the tier warmup
        # above deliberately primed hits/demotions)
        d_hit = tier1["hits_total"] - tier0["hits_total"]
        d_miss = tier1["misses_total"] - tier0["misses_total"]
        line["host_tier_hit_rate"] = (
            round(d_hit / (d_hit + d_miss), 3)
            if (d_hit + d_miss) > 0 else None
        )
        line["host_tier"] = {
            k: tier1[k]
            for k in ("budget_bytes", "bytes", "entries",
                      "demotions", "promotions", "fallbacks",
                      "evictions_total", "corrupt_total",
                      "rejected_total")
        }
    if args.working_set_mult:
        line["working_set_mult"] = args.working_set_mult
        line["working_set_prefixes"] = ws_prefixes
        line["kv_pages"] = engine.page_stats()
    if args.quality:
        # engine-side model-quality view (obs/quality.py): means over
        # every finite per-token signal, PSI drift vs the reference
        # fingerprint when one was given, validity + λ summary
        line["quality"] = engine.quality_stats()
        if args.quality_record:
            from differential_transformer_replication_tpu.obs.quality import (
                save_fingerprint,
            )

            save_fingerprint(
                args.quality_record,
                engine.quality_fingerprint(
                    meta={"model": model_cfg.model, "bench": "serve_bench"}
                ),
            )
            line["quality_record"] = args.quality_record
    print(json.dumps(line))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    print(
        f"[serve_bench] {model_cfg.model} slots={serving.num_slots} "
        f"clients={args.clients} reqs={len(completed)} "
        f"failed={n_failed} retries={retries_total[0]} wall={wall:.2f}s "
        f"out_tok/s={out_tokens / wall:.1f} "
        f"engine_stats={engine.stats} compiles={engine.compile_stats()}",
        file=sys.stderr,
    )
    assert len(completed) + n_failed == args.requests, \
        "some requests neither completed nor failed"
    # without injected faults or an admission bound nothing should fail;
    # a bounded queue may legitimately shed under closed-loop overload
    if not args.max_queue_len and not args.deadline:
        assert n_failed == 0, f"unexpected failures: {errors}"


if __name__ == "__main__":
    main()
