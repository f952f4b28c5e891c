"""A ``train_steps`` cell: the trainer's own step, fed as the trainer feeds
it, for a wall-time window.

The harness's loop stands in for ``train()``, which runs a whole recipe and
cannot run "for N seconds": offsets drawn as its ``replacement`` sampler
draws them, gathered by ``TokenWindows.batches``, one call of the jitted
step a step, windows of ``steps_per_window`` steps closed by
``jax.block_until_ready``.

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first steps (the steps `correct` judges, through the
same feed and call as every later step), and hands that same object to the
window. After the window the state is freed and the reference follows the
same first steps in float32.
"""

from __future__ import annotations

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, harness, program
from .harness import say
from .spans import SpanRecorder


def _leaf_list(tree) -> list:
    return [float(x) for x in jax.tree_util.tree_leaves(jax.device_get(tree))]


class Trainer:
    """The compiled step with its state, its corpus and its feed, all from
    the seed: the one object that set-up drives through its first steps
    and the window then drives on."""

    def __init__(self, cell: harness.Cell, seed: int, spans: SpanRecorder,
                 break_step=None) -> None:
        self.cell, self.seed, self.spans = cell, seed, spans
        self.reference = harness.load_reference(cell.config)
        traffic, config = cell.traffic, cell.config
        self.model = model = config["model"]
        self.rows = traffic["rows_per_chip"] * cell.chips
        self.seq = model["block_size"]
        harness.need(config, "train", "the train_steps driver")
        self.tcfg = program.train_config(config, self.rows, cell.chips)
        mesh = program.make_mesh(self.tcfg) if cell.chips > 1 else None
        self.repl = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self.repl = NamedSharding(mesh, PartitionSpec())
        params = self.reference.make_params(seed, model, sharding=self.repl)
        program.check_layout(params, config)
        self.state = program.train_state(params, self.tcfg, mesh)
        del params
        self.tokens = np.asarray(jax.jit(lambda k: jax.random.randint(
            k, (traffic["corpus_tokens"],), 0, model["vocab_size"],
            jnp.int32))(jax.random.key((seed + 1) % (2**31))))
        self.windows = program.token_windows(self.tokens, self.seq)
        self.step = program.train_step(self.tcfg, mesh, self.state)
        if break_step is not None:  # selftest's broken timed path
            self.step = break_step(self.step)
        self.data_rng = np.random.default_rng(seed)

    def feed(self):
        with self.spans.span("data"):
            offs = self.data_rng.integers(
                0, len(self.windows),
                size=(self.tcfg.grad_acc_steps, self.rows), dtype=np.int64)
            return offs, self.windows.batches(offs)

    def advance(self, batch):
        """One step through the window's own call; returns its metrics."""
        with self.spans.span("dispatch"):
            self.state, metrics = self.step(self.state, batch, None)
        return metrics

    def first_steps(self, n: int) -> dict:
        """Drive the first ``n`` steps and read what `correct` compares:
        every step's loss, the leaf norms of the first gradient as the
        optimizer got it (Adam's first moment after one step is
        (1 - beta1) times it), the leaf norms of the parameters' change
        after the last. Also returns the rows the steps saw."""
        beta1 = self.tcfg.beta1
        norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), t))
        sub_norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
        losses, offsets = [], []
        for k in range(n):
            offs, batch = self.feed()
            offsets.append(offs)
            losses.append(self.advance(batch)["loss"])
            if k == 0:
                first_mu = norms(program.adam_first_moment(self.state))
        p0 = self.reference.make_params(self.seed, self.model,
                                        sharding=self.repl)
        delta = sub_norms(self.state["params"], p0)
        del p0
        rows = np.stack([self.tokens[o[0][:, None] + np.arange(self.seq + 1)]
                         for o in offsets])  # (steps, rows, T+1)
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "first_grad_norms": [x / (1.0 - beta1)
                                     for x in _leaf_list(first_mu)],
                "delta_norms": _leaf_list(delta), "rows": rows}

    def free(self) -> None:
        self.state = self.step = self.windows = None


def reference_readings(cell: harness.Cell, seed: int, rows: np.ndarray,
                       quant=None) -> dict:
    """The reference through the same rows, float32 (or, for the control,
    ``quant``), in blocks of rows so that it fits beside nothing."""
    reference = harness.load_reference(cell.config)
    model = cell.config["model"]
    fn = reference.make_train_steps(
        model, cell.config["train"], cell.traffic["check"]["rows_per_block"],
        quant)
    params = reference.make_params(seed, model)
    got = jax.device_get(fn(params, jnp.asarray(rows[..., :-1]),
                            jnp.asarray(rows[..., 1:])))
    return {"losses": [float(x) for x in got["losses"]],
            "first_grad_norms": _leaf_list(got["first_grad_norms"]),
            "delta_norms": _leaf_list(got["delta_norms"])}


def run(cell: harness.Cell, env: harness.Env, args, t_start: float,
        break_step=None) -> str:
    traffic, config = cell.traffic, cell.config
    limits = harness.need(config, "correct.train", "the train_steps driver")
    chips = cell.chips
    per_window = traffic["steps_per_window"]
    spans = SpanRecorder()
    compiles = harness.CompileCount()
    laps = harness.Laps(t_start)
    if not env.rehearsal:
        program.setup_compile_cache()
    laps.lap("start+imports")

    # -- set-up: the one object, its first steps, one window of warm-up ----
    tr = Trainer(cell, args.seed, spans, break_step)
    laps.lap("weights+state+corpus")
    rows, seq = tr.rows, tr.seq
    seen = tr.first_steps(traffic["check"]["steps"])
    laps.lap("first-steps")
    # the steady state, not the first steps after a compile, is what the
    # window sees
    for _ in range(per_window):
        metrics = tr.advance(tr.feed()[1])
    jax.block_until_ready(metrics)
    laps.lap("warm-window")
    say(str(laps))

    # -- the window --------------------------------------------------------
    # A traced run profiles windows 1..trace_windows and leaves the first
    # and the later ones clean, for the host-clock rate beside the trace.
    prof = (harness.Profile(f"{cell.name}-{args.seed}", spans)
            if args.trace else None)
    traced = range(1, 1 + traffic["trace_windows"]) if prof else range(0)
    compiles_before = compiles.count
    losses, window_s = [], []
    setup_s = time.time() - t_start
    t0 = time.perf_counter()
    while True:
        w = len(window_s)
        if w == traced.start and prof is not None:
            prof.start()
        tw = time.perf_counter()
        for _ in range(per_window):
            metrics = tr.advance(tr.feed()[1])
            losses.append(metrics["loss"])
        with spans.span("block"):
            jax.block_until_ready(metrics)
        now = time.perf_counter()
        window_s.append(now - tw)
        if w + 1 == traced.stop:
            prof.stop()
        if now - t0 >= args.seconds and w + 1 >= traced.stop:
            break
    t1 = time.perf_counter()
    traced_steps = per_window * len(traced)
    compiled_in_window = compiles.count - compiles_before
    memory_peak = harness.memory_peak_bytes(env.devices)
    losses = [float(x) for x in jax.device_get(losses)]
    attempted = len(losses)
    failed = sum(1 for x in losses if not math.isfinite(x))

    # windows under the profiler are slower; the rate over all the work and
    # all the time is the end-to-end metric only in the untraced run
    steps_per_s = attempted / (t1 - t0)
    tokens_per_s_chip = steps_per_s * rows * seq / chips
    clean = [s for i, s in enumerate(window_s) if i not in traced]
    say(f"window: {attempted} steps in {t1 - t0:.3f} s, {len(window_s)} "
        f"windows of {per_window}; median window {np.median(window_s):.4f} s; "
        f"compilations inside the window: {compiled_in_window}")

    # -- free the program's state, then the reference ----------------------
    del metrics
    tr.free()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, args.seed, seen["rows"])
    say(f"reference: {len(ref['losses'])} float32 steps in "
        f"{time.perf_counter() - t_ref:.1f} s")
    rows_cmp = check.train_rows(seen, ref, limits)
    rows_cmp.append(("nonfinite_losses", float(failed), 0.0))
    rows_cmp.append(("compilations_in_window", float(compiled_in_window), 0.0))
    correct = check.judge(rows_cmp, cell.name)

    # -- the line ----------------------------------------------------------
    if not args.trace:
        e2e = {"train_tokens_per_s": tokens_per_s_chip, "setup_s": setup_s}
        return harness.result_line(
            env, correct, attempted, failed,
            harness.end_to_end(cell, e2e.__getitem__), memory_peak,
            extra={"samples": {"windows": len(window_s),
                               "steps": attempted}})
    run_ = harness.Run(cell, env, spans=spans)
    run_.values.update({
        "rows_per_chip": traffic["rows_per_chip"], "seq_len": seq,
        "trace_steps": traced_steps, "measured_window": (t0, t1),
        "measured_steps": attempted, "memory_peak_bytes": memory_peak,
        # host clock, windows outside the profiler's
        "clean_steps_per_s": (per_window * len(clean) / sum(clean)
                              if clean else None),
    })
    trace = None
    if traced_steps and not env.rehearsal:
        from . import xplane

        run_.planes = prof.load()
        trace = xplane.summary(run_.planes, spans.prefix)
    return harness.result_line(env, correct, attempted, failed,
                               harness.layer_metrics(run_), memory_peak,
                               trace=trace)


_NO_LIMITS = {"loss_rel_gap": math.inf, "first_grad_leaf_gap": math.inf,
              "param_change_leaf_gap": math.inf}


def calibrate(cell: harness.Cell, seeds, control_seeds, _seconds) -> list:
    """The numbers `correct` compares, a seed: the program's against the
    reference and, for ``control_seeds``, the float8 control's against it
    (benchmark/calibrate.py sets the limits from these)."""
    out = []
    for seed in seeds:
        tr = Trainer(cell, seed, SpanRecorder())
        seen = tr.first_steps(cell.traffic["check"]["steps"])
        tr.free()
        del tr
        ref = reference_readings(cell, seed, seen["rows"])
        rec = {"seed": seed, "loss": ref["losses"], "program": {
            n: v for n, v, _ in check.train_rows(seen, ref, _NO_LIMITS)}}
        if seed in control_seeds:
            ctl = reference_readings(cell, seed, seen["rows"], "fp8")
            rec["control"] = {n: v for n, v, _ in
                              check.train_rows(ctl, ref, _NO_LIMITS)}
        say(json.dumps(rec))
        out.append(rec)
    return out
