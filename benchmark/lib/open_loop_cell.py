"""An ``open_loop`` cell: the serving engine under its own supervisor loop,
offered requests on a schedule fixed by the seed.

In-process ``ServingEngine`` + ``EngineRunner`` (HTTP is left out; PERF.md
lists it). ONE scheduling thread sends each request through
``EngineRunner.submit`` when it is due, whether or not earlier ones have
finished; a request's clock starts when it was DUE, so a stall counts
against every request behind it. A ramp at the cell's own rate fills the
slots before the window opens and is part of warm-up. The window's
requests are those due inside it; one not finished ``drain_s`` after the
window closes has failed.

`correct` (after the window, the engine freed): a sample of the requests
the window finished, drawn from the seed with the longest in it, goes once
through the float32 reference, prompt and served tokens together; the
number compared is the widest gap by which a served token's logit lies
below the reference's best at its position. All requests are greedy.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from . import check, harness, program, traffic as traffic_lib
from .harness import say
from .spans import SpanRecorder
from .stats import STATS


@dataclass
class Sent:
    planned: traffic_lib.Planned
    due: float  # perf_counter clock
    sent: Optional[float] = None
    pending: object = None
    error: Optional[BaseException] = None

    @property
    def output(self):
        p = self.pending
        return p.result if p is not None and p.done.is_set() else None


@dataclass
class Played:
    t0: float  # the window, perf_counter clock
    t1: float
    requests: List[Sent] = field(default_factory=list)

    def in_window(self) -> List[Sent]:
        return [r for r in self.requests if self.t0 <= r.due < self.t1]


class Served:
    """The engine and its runner over given weights, warmed on the shapes
    the mix uses: every power-of-two prefill chunk up to ``prefill_chunk``,
    the decode step, both sampler shapes."""

    def __init__(self, config: dict, engine: dict, params, spans=None):
        self.settings = engine
        self.engine, self.runner = program.serving_engine(
            params, config, engine, tracer=spans)

    def warm_up(self, vocab: int, sampling: dict) -> None:
        rng = np.random.default_rng(0)
        size, pend = 1, []
        while size <= self.settings["prefill_chunk"]:
            prompt = rng.integers(0, vocab, size=size).tolist()
            pend.append(self.runner.submit(prompt, program.sampling_params(
                max_new_tokens=2, **sampling)))
            size *= 2
        for p in pend:
            if not p.done.wait(600) or p.error is not None:
                raise SystemExit(f"warm-up request failed: {p.error!r}")

    def play(self, plan: List[traffic_lib.Planned], ramp_s: float,
             seconds: float, drain_s: float, sampling: dict,
             on_window=None) -> Played:
        """Send ``plan`` on its schedule; returns once every request due
        in the window has finished or ``drain_s`` has passed since the
        window closed. ``on_window(t0)`` runs in this thread when the
        window opens (the traced run starts its profiler from it)."""
        params = [program.sampling_params(
            max_new_tokens=p.max_new_tokens, **sampling) for p in plan]
        base = time.perf_counter() + 0.05
        played = Played(base + ramp_s, base + ramp_s + seconds)
        played.requests = [Sent(p, base + p.due_s) for p in plan]

        def send() -> None:
            for req, sp in zip(played.requests, params):
                if req.due >= played.t1:
                    break
                wait = req.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                req.sent = time.perf_counter()
                try:
                    req.pending = self.runner.submit(req.planned.prompt, sp)
                except Exception as e:  # refused: counts as failed
                    req.error = e

        sender = threading.Thread(target=send, name="open-loop", daemon=True)
        sender.start()
        time.sleep(max(0.0, played.t0 - time.perf_counter()))
        if on_window is not None:
            on_window(played)
        time.sleep(max(0.0, played.t1 - time.perf_counter()))
        sender.join(30)
        if sender.is_alive():
            raise SystemExit("the open-loop sender did not stop")
        give_up = played.t1 + drain_s
        for req in played.in_window():
            if req.pending is not None:
                req.pending.done.wait(max(0.0, give_up - time.perf_counter()))
        return played

    def close(self) -> None:
        # whatever is still in flight (the ramp's tail, a request past its
        # limit) is failed by the runner's own drain, then the loop joins
        self.runner.drain(timeout=1.0)


def backlog(played: Played, share: float) -> dict:
    """Requests sent but not finished, and sent but without a first token,
    at ``share`` of the window (the knee sweep's growth measure)."""
    t = played.t0 + share * (played.t1 - played.t0)
    sent = [r for r in played.requests if r.sent is not None and r.sent <= t]
    outs = [(r, r.output) for r in sent]
    unfinished = sum(1 for r, o in outs if o is None or o.finish_time > t)
    waiting = sum(1 for r, o in outs
                  if o is None or o.first_token_time > t)
    return {"at": share, "unfinished": unfinished, "no_first_token": waiting}


def reduce_window(played: Played, drain_s: float) -> dict:
    """The end-to-end numbers of one window, from the requests' records."""
    t0, t1 = played.t0, played.t1
    window = played.in_window()
    ttft_ms, failed = [], 0
    for r in window:
        o = r.output
        if o is None or o.finish_reason != "length":
            failed += 1
            ttft_ms.append((t1 + drain_s - r.due) * 1e3)
        else:
            ttft_ms.append((o.first_token_time - r.due) * 1e3)
    gaps_ms, tokens = [], 0
    for r in played.requests:
        o = r.output
        if o is None:
            continue
        times = o.token_times
        tokens += sum(1 for t in times if t0 <= t < t1)
        gaps_ms.extend((b - a) * 1e3 for a, b in zip(times, times[1:])
                       if t0 <= b < t1)
    return {
        "attempted": len(window), "failed": failed,
        "ttft_ms": ttft_ms, "itl_ms": gaps_ms,
        "tokens_per_s": tokens / (t1 - t0),
        "gen_lag_ms": [(r.sent - r.due) * 1e3 for r in window
                       if r.sent is not None],
    }


def latency_stat(red: dict, name: str) -> float:
    """``ttft_p95_ms``, ``itl_p50_ms``, ...: ``<series>_<stat>_ms`` over the
    window's time-to-first-token or inter-token gaps (lib/stats.py:STATS),
    so that a cell can report another percentile by listing it."""
    series, stat, unit = name.rsplit("_", 2)
    if unit != "ms" or series not in ("ttft", "itl") or stat not in STATS:
        raise SystemExit(f"no serving end-to-end metric named {name!r}")
    return STATS[stat](red[series + "_ms"])


def check_sample(played: Played, seed: int, count: int) -> List[Sent]:
    """``count`` of the window's finished requests, drawn from the seed,
    the longest (prompt + served tokens) always among them."""
    done = [r for r in played.in_window()
            if r.output is not None and r.output.finish_reason == "length"]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.output.prompt) + len(r.output.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(count - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in pick]


def token_gap_inputs(sample: List[Sent], width: int):
    """``(seqs, served, mask)`` of shape (B, width): prompt then served
    tokens, the token that followed each position, and where a served
    token is judged."""
    B = len(sample)
    seqs = np.zeros((B, width), np.int32)
    served = np.zeros((B, width), np.int32)
    mask = np.zeros((B, width), bool)
    for b, r in enumerate(sample):
        o = r.output
        full = list(o.prompt) + list(o.tokens)
        n, P = len(full), len(o.prompt)
        seqs[b, :n - 1] = full[:-1]
        served[b, :n - 1] = full[1:]
        mask[b, P - 1:n - 1] = True
    return seqs, served, mask


def served_rows(sample: List[Sent], vocab: int) -> List[check.Row]:
    """What can be checked exactly on the sample: every request served as
    many tokens as it asked for, every token inside the vocabulary."""
    bad = sum(1 for r in sample
              if len(r.output.tokens) != r.planned.max_new_tokens
              or list(r.output.prompt) != r.planned.prompt
              or any(not 0 <= t < vocab for t in r.output.tokens))
    return [("requests_with_wrong_tokens", float(bad), 0.0)]


def run(cell: harness.Cell, env: harness.Env, args, t_start: float,
        break_engine=None) -> str:
    reference = harness.load_reference(cell.config)
    traffic, config = cell.traffic, cell.config
    limits = harness.need(config, "correct.serve", "the open_loop driver")
    model = config["model"]
    vocab, width = model["vocab_size"], model["block_size"]
    sampling = traffic["sampling"]
    drain_s = traffic["drain_s"]
    ramp_s = traffic["arrival"].get("ramp_s", 0.0)
    spans = SpanRecorder() if args.trace else None
    compiles = harness.CompileCount()
    laps = harness.Laps(t_start)
    if not env.rehearsal:
        program.setup_compile_cache()
    laps.lap("start+imports")

    # -- set-up ------------------------------------------------------------
    params = reference.make_params(args.seed, model)
    program.check_layout(params, config)
    served = Served(config, traffic["engine"], params, spans)
    del params
    laps.lap("weights+engine")
    if break_engine is not None:  # selftest's broken timed path
        break_engine(served.engine)
    served.warm_up(vocab, sampling)
    laps.lap("warm-up")
    plan = traffic_lib.open_loop_plan(traffic, args.seed, args.seconds, vocab)
    laps.lap("plan")
    say(str(laps) + f", ramp {ramp_s:.1f}")
    compiles_before = compiles.count
    prof = (harness.Profile(f"{cell.name}-{args.seed}", spans)
            if args.trace and not env.rehearsal else None)
    marks = {}

    def on_window(played: Played) -> None:
        marks["setup_s"] = time.time() - t_start
        if prof is not None:
            # the LAST trace_seconds of the window: a whole window is too
            # large a trace to bring back or to parse, and stop_trace holds
            # the interpreter for seconds while it writes, which stalls the
            # sender and the engine; after the window that costs only the
            # drain (in the middle it put the run over the knee: p95 lag of
            # the sender 6.6 s, my chip run, PR 23)
            span = min(traffic["trace_seconds"], args.seconds / 2)
            time.sleep(max(0.0, played.t1 - span - time.perf_counter()))
            marks["p0"] = time.perf_counter()
            prof.start()
            time.sleep(max(0.0, played.t1 - time.perf_counter()))
            marks["p1"] = time.perf_counter()
            prof.stop()

    played = served.play(plan, ramp_s, args.seconds, drain_s, sampling,
                         on_window)
    compiled_in_window = compiles.count - compiles_before
    memory_peak = harness.memory_peak_bytes(env.devices)
    num_slots = traffic["engine"]["num_slots"]
    stats = dict(served.engine.compile_stats())
    served.close()
    red = reduce_window(played, drain_s)
    say(f"window: {red['attempted']} requests due, {red['failed']} failed; "
        f"{red['tokens_per_s']:.1f} tokens/s; backlog "
        f"{[backlog(played, s) for s in (0.0, 0.5, 1.0)]}; compile stats "
        f"{stats}; compilations inside the window: {compiled_in_window}")

    # -- free the engine, then the reference --------------------------------
    sample = check_sample(played, args.seed, traffic["check"]["sample_requests"])
    served.engine = served.runner = None
    del served
    rows_cmp: List[check.Row] = []
    if sample:
        seqs, tok, mask = token_gap_inputs(sample, width)
        t_ref = time.perf_counter()
        ref_params = reference.make_params(args.seed, model)
        gaps = np.asarray(reference.make_token_gaps(model)(
            ref_params, jnp.asarray(seqs), jnp.asarray(tok)))
        del ref_params
        say(f"reference: {int(mask.sum())} served tokens of {len(sample)} "
            f"requests in {time.perf_counter() - t_ref:.1f} s")
        rows_cmp.append(("served_token_gap", float(gaps[mask].max()),
                         limits["token_gap"]))
        rows_cmp += served_rows(sample, vocab)
    else:
        rows_cmp.append(("requests_finished_in_window", 0.0, -1.0))
    rows_cmp.append(("compilations_in_window", float(compiled_in_window), 0.0))
    correct = check.judge(rows_cmp, cell.name)

    # -- the line ----------------------------------------------------------
    if not args.trace:
        e2e = {"serve_tokens_per_s": red["tokens_per_s"],
               "setup_s": marks["setup_s"]}
        return harness.result_line(
            env, correct, red["attempted"], red["failed"],
            harness.end_to_end(
                cell, lambda k: e2e[k] if k in e2e else latency_stat(red, k)),
            memory_peak,
            extra={"samples": dict(
                {"requests": red["attempted"], "token_gaps": len(red["itl_ms"])},
                **{f"{series}_{stat}_ms": latency_stat(red, f"{series}_{stat}_ms")
                   for series in ("ttft", "itl") for stat in STATS})})
    run_ = harness.Run(cell, env, spans=spans)
    t0, t1 = played.t0, played.t1
    decode = [(a, b, s) for n, a, b, s in spans.spans
              if n == "decode" and t0 <= b < t1]
    admit = {a["rid"]: t for n, t, a in spans.instants if n == "admit"}
    outs = [r.output for r in played.requests if r.output is not None]
    run_.values.update({
        "measured_window": (t0, t1), "memory_peak_bytes": memory_peak,
        "gen_lag_ms": red["gen_lag_ms"], "ttft_ms": red["ttft_ms"],
        "itl_ms": red["itl_ms"],
        "queue_wait_ms": [(admit[o.request_id] - o.submit_time) * 1e3
                          for o in outs if o.request_id in admit
                          and t0 <= admit[o.request_id] < t1],
        "decode_active_share": [s["active"] / num_slots for _, _, s in decode],
    })
    trace = None
    if prof is not None:
        from . import xplane

        p0, p1 = marks["p0"], marks["p1"]
        traced = [s for a, b, s in decode if p0 <= a and b <= p1]
        live = sum(len(o.prompt) + j for o in outs
                   for j, t in enumerate(o.token_times) if j and p0 <= t < p1)
        run_.planes = prof.load()
        run_.values.update({
            "trace_steps": max(1, len(traced)),
            "decode_rows": (sum(s["active"] for s in traced) / len(traced)
                            if traced else None),
            "decode_live_positions": live / len(traced) if traced else None,
        })
        trace = xplane.summary(run_.planes, spans.prefix)
    return harness.result_line(env, correct, red["attempted"], red["failed"],
                               harness.layer_metrics(run_), memory_peak,
                               trace=trace)


def calibrate(cell: harness.Cell, seeds, control_seeds, seconds) -> list:
    """The served-token gap a seed, of the program and, for
    ``control_seeds``, of the float8 control at the same prompts and
    tokens: short windows at the cell's own load on one engine whose
    weights are swapped a seed (benchmark/calibrate.py sets the limit from
    these)."""
    reference = harness.load_reference(cell.config)
    t, model = cell.traffic, cell.config["model"]
    served = Served(cell.config, t["engine"],
                    reference.make_params(seeds[0], model))
    served.warm_up(model["vocab_size"], t["sampling"])
    out = []
    for seed in seeds:
        params = reference.make_params(seed, model)
        served.engine.params = params
        plan = traffic_lib.open_loop_plan(t, seed, seconds, model["vocab_size"])
        played = served.play(plan, 2.0, seconds, t["drain_s"], t["sampling"])
        time.sleep(1.0)  # let the tail of the window's traffic retire
        sample = check_sample(played, seed, t["check"]["sample_requests"])
        seqs, tok, mask = token_gap_inputs(sample, model["block_size"])
        red = reduce_window(played, t["drain_s"])
        rec = {"seed": seed, "requests": red["attempted"],
               "failed": red["failed"], "tokens_judged": int(mask.sum())}
        for name, quant in (("program", None), ("control", "fp8")):
            if quant and seed not in control_seeds:
                continue
            gaps = np.asarray(reference.make_token_gaps(model, quant)(
                params, jnp.asarray(seqs), jnp.asarray(tok)))[mask]
            rec[name] = {"served_token_gap": float(gaps.max()),
                         "tokens_off_best": int((gaps > 0).sum())}
        say(json.dumps(rec))
        out.append(rec)
    served.close()
    return out
