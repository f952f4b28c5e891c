#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: train, reference, sync, serve
    python chip_smoke.py --multichip  # four chips: DP-4 trainer vs one chip

It drives the main path once through the entry points a user calls, at
the full width of the ``diff`` recipe (8L/768d/4 heads, T=512, micro-batch
32, vocab 12000, bf16, Pallas attention and FFN), with weights and data
made from a seed:

- *train*: ``train.py``'s parser and ``train()`` for a handful of steps on
  a corpus generated into the work directory, with one eval and one
  checkpoint. Checks: the model that trains is 12000 wide, every loss is
  finite and the last is below the first, the compiled step holds the
  kernels (``tpu_custom_call`` count), nothing compiles after warm-up.
- *reference*: the same seed and batch through ``attention_impl=xla,
  ffn_impl=xla``; first-step loss and gradient norm agree with the Pallas
  path within the bf16 tolerance below.
- *sync*: windows of ten train steps closed by ``jax.block_until_ready``
  and by a scalar read-back, timed side by side.
- *kv_write*: the decode step's K/V write kernel (``ops/kv_write.py``)
  alone, compiled, at the serve cells' leaf shapes, over the patterns of
  slots that write and slots that keep their row, against NumPy bit for
  bit. A kept slot's grid step leans on what the chip's pipeline keeps in
  VMEM between steps, which the interpreter the tests run cannot show.
- *serve*: ``python -m ...serving.server --checkpoint <the one train wrote>``
  once per decode path (XLA and the Pallas decode kernel; contiguous and
  paged; bf16 and int8 KV; the multi-query kernel under ``--spec-mode``).
  Each answers greedy ``/generate`` requests of mixed prompt lengths and
  drains on SIGTERM. Checks: ``/health`` healthy with zero engine restarts
  and one decode compile, and Pallas replies agree with XLA replies by
  the criterion of :func:`replies_agree`.

One process uses the chip at a time: this parent never imports JAX, and
runs each phase as a child, one after the other. Wall times, compile
times, rates and memory peaks are printed per phase; they are smoke
outputs, not benchmark numbers. It exits non-zero when JAX finds no TPU
or any phase fails. The last line of standard output is then
``{"ok": true, "device": {"platform", "kind", "count"}}`` as the children
reported the device.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PKG = "differential_transformer_replication_tpu"
sys.path.insert(0, str(ROOT))

# The `diff` recipe at full width. Depth and width are the recipe's own;
# only the step count and the corpus are cut to smoke size.
FULL = {
    "model": "diff", "n_layer": 8, "n_embd": 768, "n_head": 4,
    "block_size": 512, "micro_batch": 32, "vocab": 12000,
    "dtype": "bfloat16",
    "steps": 30, "eval_iters": 4, "warmup": 4, "lr": 6e-4,
    "corpus_docs": 20000, "corpus_words": 30000,
    # serve: at least one prompt >= 256 tokens; chunk sizes 128/64/16
    "prompt_lens": (320, 64, 16), "new_tokens": 64, "num_slots": 8,
    "page_size": 8,  # smallest the verify skill and serve_bench use
    "kv_slots": 256,  # kv_write: the serve cells' pool
    # --multichip: the batch is per optimizer step, before the DP split
    "dp": 4, "dp_steps": 8,
}
SEED = 1337

# bf16 tolerances, stated before the run. One bf16 rounding is 2^-8
# relative; the Pallas and XLA paths round at different points of an
# 8-layer model, so first-step losses (a mean over 16k tokens) agree far
# inside 0.5%, gradient norms inside 5%, and a token's log-probability
# inside 0.25 nat. DP-4 against one chip changes only the order of fp32
# reductions: 1% per step.
LOSS_RTOL = 5e-3
GRAD_NORM_RTOL = 5e-2
LOGPROB_TOL = 0.25
DP_LOSS_RTOL = 1e-2

# The whole run, compilation included, has 1200 s; every wait below is
# cut to what is left of this.
DEADLINE_S = 1140
SERVER_START_TIMEOUT_S = 240
_t_begin = time.monotonic()


def time_left() -> float:
    left = DEADLINE_S - (time.monotonic() - _t_begin)
    if left <= 0:
        raise SmokeFailure(f"the run passed {DEADLINE_S}s")
    return left


class SmokeFailure(Exception):
    """A phase ran and its result is wrong."""


def say(phase: str, msg: str) -> None:
    print(f"[smoke:{phase}] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def write_corpus(path: Path, num_docs: int, n_words: int, seed: int) -> None:
    """A seeded corpus, one document per line, with enough distinct words
    for BPE (min_frequency 2) to reach the recipe's 12,000 tokens: the
    stock synthetic corpus stops at 499, and ``train()`` narrows the model
    to what the tokenizer reached. Pseudo-words of 2-4 syllables drawn
    with Zipf-like frequencies, so a few steps already lower the loss."""
    import numpy as np

    rng = np.random.default_rng(seed)
    onsets = list("bcdfghjklmnprstvwz") + ["ch", "sh", "th", "br", "st", "tr"]
    vowels = list("aeiou") + ["ai", "ea", "oo"]
    codas = ["", "", "", "n", "r", "s", "t", "l", "m", "k"]
    syl = np.array([o + v + c for o in onsets for v in vowels for c in codas])
    n_syl = rng.integers(2, 5, size=n_words)
    picks = rng.integers(0, len(syl), size=(n_words, 4))
    words = np.unique(
        ["".join(syl[picks[i, : n_syl[i]]]) for i in range(n_words)]
    )
    rng.shuffle(words)
    p = 1.0 / (np.arange(len(words)) + 10.0)
    p /= p.sum()
    lens = rng.integers(20, 60, size=num_docs)
    draws = rng.choice(len(words), size=int(lens.sum()), p=p)
    with open(path, "w", encoding="utf-8") as f:
        start = 0
        for n in lens:
            f.write(" ".join(words[draws[start:start + n]]) + ".\n")
            start += n


def train_argv(work: Path, size: dict, *, micro_batch: int, steps: int,
               tag: str, extra: tuple = ()) -> list:
    """Flags for ``train.py``'s own parser."""
    return [str(a) for a in (
        "--model", size["model"], "--n-layer", size["n_layer"],
        "--n-embd", size["n_embd"], "--n-head", size["n_head"],
        "--block-size", size["block_size"],
        "--micro-batch-size", micro_batch,
        "--compute-dtype", size["dtype"],
        "--attention-impl", "pallas", "--ffn-impl", "pallas",
        "--dataset", work / "corpus.txt",
        "--num-train-samples", size["corpus_docs"],
        "--tokenizer-dir", work / "tok", "--vocab-size", size["vocab"],
        "--max-iters", steps, "--eval-interval", steps,
        "--eval-iters", size["eval_iters"],
        "--warmup-iters", size["warmup"], "--learning-rate", size["lr"],
        "--seed", SEED,
        "--checkpoint-path", work / f"{tag}.ckpt",
        "--last-checkpoint-path", "",
        "--metrics-path", work / f"{tag}.metrics.jsonl",
        *extra,
    )]


# ---------------------------------------------------------------------------
# phases that hold the chip (run inside a child process)
# ---------------------------------------------------------------------------


class CompileClock:
    """Backend compilations of this process and the time they took, read
    from JAX's own monitoring events (a persistent-cache hit is counted
    too, at the time the retrieval took)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.durations = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.durations.append(duration)

    def since(self, mark: int) -> dict:
        d = self.durations[mark:]
        return {"programs": len(d), "total_s": round(sum(d), 2),
                "largest_s": round(max(d, default=0.0), 2)}


def _run_trainer(argv: list):
    """``train.py``'s path: same parser, same TrainConfig, ``train()``.
    Only ``log_interval`` is changed, so that every step's loss lands in
    metrics.jsonl. Returns (cfg, final state, per-step records)."""
    import train as train_cli
    from differential_transformer_replication_tpu.train.trainer import train

    cfg = train_cli.config_from_args(train_cli.build_parser().parse_args(argv))
    cfg = cfg.replace(log_interval=1)
    state = train(cfg)
    with open(cfg.metrics_path) as f:
        rows = [json.loads(line) for line in f]
    return cfg, state, [r for r in rows if "loss" in r and "iter" in r]


def _lm_head_width(state) -> int:
    return int(state["params"]["lm_head"]["w"].shape[-1])


def _check_losses(phase: str, steps: list, n_expected: int) -> list:
    losses = [r["loss"] for r in steps]
    check(len(losses) == n_expected,
          f"{len(losses)} logged steps, expected {n_expected}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    say(phase, "loss per step " + " ".join(f"{x:.4f}" for x in losses))
    return losses


def _count_kernels(phase: str, label: str, jitted, *args) -> tuple:
    """Lower and compile ``jitted`` for ``args`` and count what the
    compiled text holds. The program is the one the trainer ran, so with
    the compile cache on this is a retrieval."""
    text = jitted.lower(*args).compile().as_text()
    kernels = text.count("tpu_custom_call")
    all_reduces = text.count("all-reduce-start") or text.count("all-reduce(")
    say(phase, f"{label}: tpu_custom_call {kernels}, all-reduce {all_reduces}")
    return kernels, all_reduces


def _random_batch(cfg, seed: int, micro_batch: int) -> dict:
    import jax
    import jax.numpy as jnp

    m = cfg.resolved_model()
    x = jax.random.randint(jax.random.PRNGKey(seed),
                           (1, micro_batch, m.block_size), 0, m.vocab_size)
    return {"x": x, "y": jnp.roll(x, -1, axis=-1)}


def train_phases(work: Path, size: dict, require_device) -> dict:
    """train + reference + sync, in the one process that holds the chip
    (they share the compiled step)."""
    device = require_device("chip_smoke")
    say("train", f"device {json.dumps(device)}")

    import jax

    from differential_transformer_replication_tpu.data.native import (
        native_available,
    )
    from differential_transformer_replication_tpu.ops.flash import (
        default_blocks,
        tuned_block_key,
    )
    from differential_transformer_replication_tpu.train.step import (
        create_train_state,
        make_train_step,
    )
    from differential_transformer_replication_tpu.utils.device import (
        peak_memory_bytes,
        setup_compile_cache,
    )

    say("train", f"compile cache at {setup_compile_cache()}")
    clock = CompileClock()
    key = tuned_block_key(device["kind"])
    say("train", f"flash tiles {default_blocks()} "
                 f"(device kind {device['kind']!r} -> tuned key {key!r})")
    if device["platform"] == "tpu":
        check(key is not None,
              f"device kind {device['kind']!r} matches no tuned flash tiles")
    say("train", "epoch permutation: "
                 + ("native library" if native_available() else "numpy mirror"))

    write_corpus(work / "corpus.txt", size["corpus_docs"],
                 size["corpus_words"], SEED)
    result = {"device": device}

    # -- train ---------------------------------------------------------
    t0 = time.perf_counter()
    argv = train_argv(work, size, micro_batch=size["micro_batch"],
                      steps=size["steps"], tag="smoke")
    cfg, state, steps = _run_trainer(argv)
    wall = time.perf_counter() - t0
    width = _lm_head_width(state)
    say("train", f"vocab_size {width}")
    check(width == size["vocab"],
          f"the model that trained is {width} wide, not {size['vocab']}")
    cfg = cfg.replace(vocab_size=width)  # what train() resolved
    losses = _check_losses("train", steps, size["steps"])
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    compiles = [r.get("compile_events") for r in steps]
    check(all(c == 1 for c in compiles),
          f"the train step recompiled after warm-up: {compiles}")
    say("train", "compiles after warm-up 0 (compile_events 1 at every step)")
    check((work / "smoke.ckpt" / "meta.json").exists(), "no checkpoint written")
    # every record is written after that step's loss was read back, so
    # the gaps between their timestamps are whole steps, logging included
    gaps = sorted(b["ts"] - a["ts"] for a, b in zip(steps[2:], steps[3:]))
    tokens = size["micro_batch"] * size["block_size"]
    say("train", f"wall {wall:.1f}s, compile {json.dumps(clock.since(0))}, "
                 f"~{tokens / gaps[len(gaps) // 2]:.0f} tok/s (median gap "
                 f"between logged steps, logging every step), "
                 f"peak_bytes_in_use {peak_memory_bytes()}")

    step = make_train_step(cfg)
    batch = _random_batch(cfg, SEED + 1, size["micro_batch"])
    kernels, _ = _count_kernels("train", "compiled train step", step,
                                state, batch, None)
    if device["platform"] == "tpu":
        check(kernels > 0, "the compiled train step holds no Pallas kernel")
    result.update(vocab_size=width, losses=losses, tpu_custom_call=kernels)

    # -- reference -----------------------------------------------------
    t0, mark = time.perf_counter(), len(clock.durations)
    first = {}
    for impl in ("pallas", "xla"):
        cfg_i = cfg.replace(model=cfg.model.replace(attention_impl=impl,
                                                    ffn_impl=impl))
        st = create_train_state(jax.random.PRNGKey(SEED), cfg_i)
        _, m = make_train_step(cfg_i)(st, batch, None)
        first[impl] = (float(m["loss"]), float(m["grad_norm"]))
        say("reference", f"{impl}: first-step loss {first[impl][0]:.5f}, "
                         f"grad norm {first[impl][1]:.5f}")
        del st, m
    d_loss = abs(first["pallas"][0] - first["xla"][0]) / abs(first["xla"][0])
    d_norm = abs(first["pallas"][1] - first["xla"][1]) / abs(first["xla"][1])
    say("reference", f"relative difference: loss {d_loss:.2e} "
                     f"(tolerance {LOSS_RTOL}), grad norm {d_norm:.2e} "
                     f"(tolerance {GRAD_NORM_RTOL}); wall "
                     f"{time.perf_counter() - t0:.1f}s, compile "
                     f"{json.dumps(clock.since(mark))}")
    check(d_loss <= LOSS_RTOL and d_norm <= GRAD_NORM_RTOL,
          "Pallas and XLA first steps disagree")
    result["reference"] = {"loss_rel": d_loss, "grad_norm_rel": d_norm}

    # -- sync ----------------------------------------------------------
    mark = len(clock.durations)
    closers = {
        "block_until_ready": lambda m: jax.block_until_ready(m["loss"]),
        "scalar_readback": lambda m: float(m["loss"]),
    }
    state, m = step(state, batch, None)  # this jit object's warm-up
    float(m["loss"])
    mark_steady = len(clock.durations)
    windows = {name: [] for name in closers}
    for _ in range(3):
        for name, close in closers.items():
            t0 = time.perf_counter()
            for _ in range(10):
                state, m = step(state, batch, None)
            close(m)
            windows[name].append(round(time.perf_counter() - t0, 4))
    for name, secs in windows.items():
        say("sync", f"ten steps closed by {name}: {secs} s")
    check(len(clock.durations) == mark_steady,
          "a compile happened inside the timed windows")
    say("sync", f"compile {json.dumps(clock.since(mark))}, "
                f"peak_bytes_in_use {peak_memory_bytes()}")
    result["sync_windows_s"] = windows
    return result


def multichip_phase(work: Path, size: dict, require_device) -> dict:
    """The recipe trainer data-parallel over ``size['dp']`` chips through
    the overlapped DP step, against the one-chip run of the same seed and
    data with gradient accumulation in place of the split."""
    device = require_device("chip_smoke --multichip")
    say("multichip", f"device {json.dumps(device)}")
    dp = size["dp"]
    check(device["count"] == dp, f"{device['count']} devices, need {dp}")

    import jax

    from differential_transformer_replication_tpu.parallel import create_mesh
    from differential_transformer_replication_tpu.parallel.dp_step import (
        make_sharded_train_step,
        overlap_eligible,
    )
    from differential_transformer_replication_tpu.utils.device import (
        setup_compile_cache,
    )

    say("multichip", f"compile cache at {setup_compile_cache()}")
    clock = CompileClock()
    write_corpus(work / "corpus.txt", size["corpus_docs"],
                 size["corpus_words"], SEED)
    micro, steps = size["micro_batch"], size["dp_steps"]

    t0 = time.perf_counter()
    cfg, state, dp_steps = _run_trainer(train_argv(
        work, size, micro_batch=micro * dp, steps=steps, tag="dp",
        extra=("--data-parallel", dp)))
    check(overlap_eligible(cfg), "the run did not take the overlapped DP step")
    dp_losses = _check_losses("multichip", dp_steps, steps)
    say("multichip", f"DP-{dp} wall {time.perf_counter() - t0:.1f}s, "
                     f"compile {json.dumps(clock.since(0))}")
    cfg = cfg.replace(vocab_size=_lm_head_width(state))

    # the state and the batch really span the chips
    devices = set(jax.devices())
    spans = {frozenset(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(state)}
    check(spans == {frozenset(devices)},
          f"state leaves do not span all {dp} devices: "
          f"{sorted(len(s) for s in spans)}")
    peaks = [d.memory_stats() for d in jax.devices()]
    if all(peaks):  # None on the CPU backend (rehearsal)
        peaks = [p["peak_bytes_in_use"] for p in peaks]
        say("multichip", f"peak_bytes_in_use per device {peaks}")
        check(min(peaks) > 0.5 * max(peaks),
              "some device held much less than the others")
    mesh = create_mesh(cfg.mesh)
    step = make_sharded_train_step(cfg, mesh, state)
    batch = _random_batch(cfg, SEED + 1, micro * dp)
    compiled_for = step.jitted.lower(state, batch, None).compile()
    x_sharding = compiled_for.input_shardings[0][1]["x"]
    check(set(x_sharding.device_set) == devices
          and not x_sharding.is_fully_replicated,
          f"the batch is not split over the devices: {x_sharding}")
    say("multichip", f"state on {dp} devices (replicated), batch sharding "
                     f"{x_sharding.spec} over {len(x_sharding.device_set)}")
    kernels, all_reduces = _count_kernels(
        "multichip", f"compiled DP-{dp} step", step.jitted, state, batch, None)
    if device["platform"] == "tpu":
        check(kernels > 0, "the compiled DP step holds no Pallas kernel")
    check(all_reduces > 0, "the compiled DP step holds no all-reduce")
    del state, step, compiled_for

    t0, mark = time.perf_counter(), len(clock.durations)
    _, _, one_steps = _run_trainer(train_argv(
        work, size, micro_batch=micro, steps=steps, tag="one",
        extra=("--grad-acc-steps", dp)))
    one_losses = _check_losses("multichip", one_steps, steps)
    say("multichip", f"one-chip wall {time.perf_counter() - t0:.1f}s, "
                     f"compile {json.dumps(clock.since(mark))}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(dp_losses, one_losses))
    say("multichip", f"DP-{dp} vs one chip, worst relative loss difference "
                     f"over {steps} steps {worst:.2e} "
                     f"(tolerance {DP_LOSS_RTOL})")
    check(worst <= DP_LOSS_RTOL, "DP and one-chip losses disagree")
    return {"device": device, "dp_losses": dp_losses,
            "one_losses": one_losses, "tpu_custom_call": kernels}


# bf16 logits of one toy model through two backends of its state-space
# mixers: the kernels keep the recurrence in float32 as the XLA path does,
# so what differs is the order of a few float32 sums
JAMBA_LOGIT_TOL = 0.05


def jamba_phase(work: Path, size: dict, require_device) -> dict:
    """A toy model of the ``jamba`` family (Mamba mixers with a recurrent
    state a slot beside a multi-query attention layer) through the engine,
    once a ``ssm_impl``: prefill in ladder chunks, decoding through the
    slot pool with slots reused, one decode program, and the ``pallas``
    path's logits against the ``xla`` path's at the same tokens."""
    del size
    device = require_device("chip_smoke jamba")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        ServingConfig,
    )
    from differential_transformer_replication_tpu.models import init_model
    from differential_transformer_replication_tpu.models.decode import (
        forward_chunk,
        forward_decode_pool,
        init_cache,
    )
    from differential_transformer_replication_tpu.serving.engine import (
        ServingEngine,
    )

    cfg = ModelConfig(
        model="jamba", vocab_size=2048, n_embd=256, n_head=4, kv_heads=1,
        n_layer=4, block_size=256, ffn_hidden=512,
        tie_embeddings=True, attn_layer_period=4, attn_layer_offset=1,
        param_dtype="bfloat16")
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 2048, size=int(n)).tolist()
               for n in rng.integers(8, 120, size=12)]
    served = {}
    for impl in ("xla", "pallas"):
        eng = ServingEngine(params, cfg.replace(ssm_impl=impl), ServingConfig(
            num_slots=4, prefill_chunk=32, prefill_budget=128))
        outs = eng.generate(prompts, max_new_tokens=16, temperature=0.0)
        check(all(len(o.tokens) == 16 and o.finish_reason == "length"
                  for o in outs), f"jamba {impl}: a request came back short")
        stats = eng.compile_stats()
        check(stats["decode"] == 1 and stats["state_reset"] == 1,
              f"jamba {impl}: compile stats {stats}")
        check(eng.stats["state_resets"] == len(prompts),
              f"jamba {impl}: {eng.stats['state_resets']} resets")
        served[impl] = [list(o.tokens) for o in outs]
        say("jamba", f"{impl}: {len(prompts)} requests over 4 slots, "
                     f"compiles {json.dumps(stats)}")
    same = sum(a == b for a, b in zip(served["xla"], served["pallas"]))
    say("jamba", f"{same} of {len(prompts)} greedy replies identical "
                 "between the two paths (a bf16 tie may differ)")
    # the same tokens through both paths: prefill 64 + 32, then 8 steps
    idx = jnp.asarray(rng.integers(0, 2048, size=(4, 104)))
    logits = {}
    for impl in ("xla", "pallas"):
        c = cfg.replace(ssm_impl=impl)
        cache, outs, pos = init_cache(c, 4), [], 0
        for n in (64, 32):
            lg, cache = jax.jit(
                lambda t, cache, pos=pos, c=c: forward_chunk(
                    params, t, pos, cache, c))(idx[:, pos:pos + n], cache)
            outs.append(lg)
            pos += n
        step = jax.jit(lambda t, p, cache, c=c: forward_decode_pool(
            params, t, p, cache, c))
        for t in range(pos, idx.shape[1]):
            lg, cache = step(idx[:, t], jnp.full((4,), t), cache)
            outs.append(lg[:, None])
        logits[impl] = np.asarray(jnp.concatenate(outs, 1), np.float32)
    worst = float(np.abs(logits["xla"] - logits["pallas"]).max())
    say("jamba", f"worst logit difference pallas vs xla {worst:.4f} "
                 f"(tolerance {JAMBA_LOGIT_TOL}, logits' std "
                 f"{logits['xla'].std():.2f})")
    check(np.isfinite(worst) and worst <= JAMBA_LOGIT_TOL,
          "the two ssm_impl paths disagree")
    return {"device": device, "identical_replies": same,
            "worst_logit_gap": worst}


def kv_write_leaves(slots: int) -> list:
    """(name, leaf shape, pool axis, dtype) of the pools the serve cells
    hold: the diff recipe's K and V (ring on the lanes), an int8 K with
    its scale plane, and the jamba cell's K (a head of 128: ring on the
    sublanes)."""
    return [
        ("diff-k", (2, slots, 4, 512, 96), 1, "bfloat16"),
        ("diff-v", (slots, 4, 512, 192), 0, "bfloat16"),
        ("diff-k-int8", (2, slots, 4, 512, 96), 1, "int8"),
        ("diff-k-scale", (2, slots, 4, 512), 1, "float32"),
        ("jamba-k", (1, slots, 1, 2048, 128), 1, "bfloat16"),
    ]


def kv_write_patterns(slots: int, M: int, rng) -> list:
    """(name, targets) over ``slots`` slots of a ring of ``M``: -1 keeps
    a slot's row. Every place a kept grid step can stand in: nowhere,
    everywhere, before the first writer, between writers, behind the
    last; and the chat cell's one slot in eight."""
    def at(writers):
        targets = [-1] * slots
        for b in writers:
            targets[b] = int(rng.integers(0, M))
        return targets

    same = [-1] * slots
    same[slots // 3], same[2 * slots // 3] = M // 2 + 1, M // 2 + 2
    return [
        ("none", at([])),
        ("last-only", at([slots - 1])),
        ("first-only", at([0])),
        ("kept-then-writers", at(range(slots // 3, slots, 2))),
        ("all", at(range(slots))),
        ("same-block", same),
        ("one-in-eight", at(sorted(rng.choice(
            slots, size=max(1, slots // 8), replace=False).tolist()))),
    ]


def kv_write_phase(work: Path, size: dict, require_device) -> dict:
    """``write_rows`` compiled, a leaf and a pattern at a time, on the
    pool that the call before it gave back (donated, as the engine's
    decode program donates it): the result is the leaf's own buffer, and
    holds NumPy's answer to the bit."""
    del work
    device = require_device("chip_smoke kv_write")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from differential_transformer_replication_tpu.ops.kv_write import (
        write_rows,
    )

    rng = np.random.default_rng(SEED)
    on_chip = device["platform"] == "tpu"

    def draw(shape, dtype):
        if dtype == "int8":
            return rng.integers(-127, 128, shape, dtype=np.int8)
        return rng.standard_normal(shape, dtype=np.float32)

    def bits(a):
        a = np.asarray(a)
        return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])

    checked = {}
    for name, shape, axis, dtype in kv_write_leaves(size["kv_slots"]):
        M = shape[axis + 2]
        write = jax.jit(lambda l, r, t, axis=axis: write_rows(l, r, t, axis),
                        donate_argnums=0)
        leaf = jnp.asarray(draw(shape, dtype), dtype)
        want = np.array(leaf)
        for pattern, targets in kv_write_patterns(shape[axis], M, rng):
            rows = jnp.asarray(
                draw(shape[:axis + 2] + shape[axis + 3:], dtype), dtype)
            rows_np = np.asarray(rows)
            for b, t in enumerate(targets):
                if t >= 0:
                    lead = (slice(None),) * axis + (b,)
                    want[lead + (slice(None), t)] = rows_np[lead]
            given, held = leaf, leaf.unsafe_buffer_pointer()
            leaf = write(given, rows, jnp.asarray(targets, jnp.int32))
            leaf.block_until_ready()
            check(given.is_deleted(),
                  f"{name} {pattern}: the donated leaf is still alive")
            # the CPU's interpreter builds its result elsewhere
            check(not on_chip or leaf.unsafe_buffer_pointer() == held,
                  f"{name} {pattern}: the result is not the donated buffer")
            wrong = int((bits(leaf) != bits(want)).sum())
            check(wrong == 0, f"{name} {pattern}: {wrong} values differ from "
                              f"NumPy's ({sum(t >= 0 for t in targets)} of "
                              f"{len(targets)} slots write)")
            checked.setdefault(name, []).append(pattern)
        say("kv_write", f"{name} {'x'.join(map(str, shape))} {dtype}: bit for "
                        f"bit over {', '.join(checked[name])}")
    return {"device": device, "checked": checked}


CHILD_PHASES = {"train": train_phases, "multichip": multichip_phase,
                "jamba": jamba_phase, "kv_write": kv_write_phase}


# ---------------------------------------------------------------------------
# serve (driven from the parent: the servers are the children)
# ---------------------------------------------------------------------------


def serve_variants(page: int) -> list:
    """(name, server flags, the variant its replies are held against)."""
    pallas = ("--decode-attention-impl", "pallas")
    paged = ("--kv-page-size", str(page))
    int8 = ("--kv-cache-dtype", "int8")
    return [
        ("xla", (), None),
        ("pallas", pallas, "xla"),
        ("pallas-paged", pallas + paged, "xla"),
        # the multi-query (speculative verify) kernel
        ("pallas-paged-spec", pallas + paged + (
            "--spec-mode", "ngram", "--spec-verify", "batched"), "xla"),
        ("xla-int8", int8, None),
        ("pallas-int8", pallas + int8, "xla-int8"),
        ("pallas-paged-int8", pallas + paged + int8, "xla-int8"),
    ]


def _http(method: str, url: str, body=None, timeout: float = 10.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def replies_agree(ref: dict, got: dict, tol: float = LOGPROB_TOL) -> int:
    """The stated criterion for two greedy replies to one prompt. Walk the
    tokens: where both chose the same token, its log-probabilities differ
    by at most ``tol``. At the first position where they differ, each
    one's choice must be a near-tie for the other (among its top
    alternatives, within ``tol`` of its best) — after a fork the two
    sequences legitimately differ, so the walk ends there. The first
    token is always held to this. Returns the agreed prefix length;
    raises :class:`SmokeFailure` otherwise."""
    n = min(len(ref["tokens"]), len(got["tokens"]))
    for i in range(n):
        a, b = ref["tokens"][i], got["tokens"][i]
        if a == b:
            gap = abs(ref["token_logprobs"][i] - got["token_logprobs"][i])
            check(gap <= tol, f"token {i}: same choice, log-probabilities "
                              f"{gap:.3f} apart (tolerance {tol})")
            continue
        for mine, theirs in ((ref, b), (got, a)):
            top = dict(map(tuple, mine["top_logprobs"][i]))
            best = max(top.values())
            check(theirs in top and best - top[theirs] <= tol,
                  f"token {i}: choices {a} and {b} are not a near-tie")
        return i
    return n


def _stop(proc: subprocess.Popen, sig=signal.SIGTERM, wait: float = 60.0):
    """Signal the child's whole process group and reap it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            pass
    try:
        return proc.wait(timeout=wait)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        return proc.wait()


def serve_one(name: str, flags: tuple, work: Path, size: dict,
              prompts: list, device: dict) -> list:
    """Start one server on the checkpoint the train phase wrote, ask it
    the prompts (concurrently: one pool, several slots), read /health,
    drain it with SIGTERM. Returns the replies in prompt order."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    log_path = work / f"server-{name}.log"
    cmd = [sys.executable, "-m", f"{PKG}.serving.server",
           "--checkpoint", str(work / "smoke.ckpt"),
           "--tokenizer", str(work / "tok"), "--port", str(port),
           "--num-slots", str(size["num_slots"]), *flags]
    t0 = time.perf_counter()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        while True:
            check(proc.poll() is None,
                  f"server exited {proc.returncode} before it was ready")
            check(time.perf_counter() - t0
                  < min(SERVER_START_TIMEOUT_S, time_left()),
                  "server not ready in time")
            try:
                if _http("GET", f"{base}/ready", timeout=2.0).get("ready"):
                    break
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.5)
        t_ready = time.perf_counter()
        replies, errors = [None] * len(prompts), []
        budget = time_left()  # first requests compile every shape

        def ask(i):
            try:
                replies[i] = _http("POST", f"{base}/generate", {
                    "prompt_ids": prompts[i], "temperature": 0.0,
                    "max_new_tokens": size["new_tokens"], "logprobs": 5,
                }, timeout=budget)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(f"prompt {i}: {e!r}")

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_done = time.perf_counter()
        check(not errors, "; ".join(errors))
        health = _http("GET", f"{base}/health")
        rc = _stop(proc)
    finally:
        _stop(proc, signal.SIGKILL)
    generated = sum(len(r["tokens"]) for r in replies)
    say("serve", f"{name}: status {health['status']}, restarts "
                 f"{health['restarts']}, compiles {health.get('compiles')}, "
                 f"{generated} tokens generated, ready after "
                 f"{t_ready - t0:.1f}s, requests {t_done - t_ready:.1f}s "
                 f"(compiles included), peak_bytes_in_use "
                 f"{health['device']['peak_bytes_in_use']}, exit {rc}"
                 + (f", spec {health['spec']}" if "spec" in health else ""))
    served_on = {k: health["device"][k] for k in device}
    check(served_on == device, f"served on {served_on}, trained on {device}")
    check(health["status"] == "healthy", f"status {health['status']}")
    check(health["restarts"] == 0,
          f"{health['restarts']} engine restart(s): see {log_path}")
    decode = health["compiles"]["decode"]
    if "--spec-mode" in flags:  # verify steps replace most decode steps
        check(decode <= 1 and health["compiles"]["spec_decode"] >= 1,
              f"compiles {health['compiles']}")
    else:
        check(decode == 1, f"compiles.decode {decode}")
    for r in replies:
        check(len(r["tokens"]) == size["new_tokens"]
              and r["finish_reason"] == "length",
              f"reply of {len(r['tokens'])} tokens, {r['finish_reason']}")
    check(rc == 0, f"server exited {rc} after SIGTERM")
    return replies


def serve_phase(work: Path, size: dict, device: dict) -> None:
    import random

    rng = random.Random(SEED)
    prompts = [[rng.randrange(2, size["vocab"]) for _ in range(n)]
               for n in size["prompt_lens"]]
    replies = {}
    for name, flags, against in serve_variants(size["page_size"]):
        replies[name] = serve_one(name, flags, work, size, prompts, device)
        if against is None:
            continue
        prefixes = [replies_agree(a, b)
                    for a, b in zip(replies[against], replies[name])]
        say("serve", f"{name} agrees with {against}: common greedy prefix "
                     f"{prefixes} of {size['new_tokens']} tokens, every "
                     f"compared log-probability within {LOGPROB_TOL}")


# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------


def run_child(phase: str, work: Path) -> dict:
    """Run one chip-holding phase as a child of this JAX-free parent; its
    output goes straight to ours. Returns what it wrote."""
    proc = subprocess.Popen(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--phase", phase,
         "--work", str(work)],
        cwd=ROOT, start_new_session=True)
    try:
        rc = proc.wait(timeout=time_left())
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {phase} ran out of time")
    finally:
        _stop(proc, signal.SIGKILL)
    check(rc == 0, f"phase {phase} exited {rc}")
    return json.loads((work / f"{phase}.json").read_text())


def child_main(phase: str, work: Path, size: dict = FULL) -> int:
    from differential_transformer_replication_tpu.utils.device import (
        require_tpu,
    )

    try:
        result = CHILD_PHASES[phase](work, size, require_tpu)
    except SmokeFailure as e:
        say(phase, f"FAILED: {e}")
        return 1
    (work / f"{phase}.json").write_text(json.dumps(result))
    return 0


def main(argv=None, *, run_child=run_child, serve_phase=serve_phase,
         size: dict = FULL) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--multichip", action="store_true",
                   help="run only the four-chip data-parallel trainer and "
                        "the one-chip run it is compared with")
    p.add_argument("--phase", choices=sorted(CHILD_PHASES),
                   help=argparse.SUPPRESS)  # this script as its own child
    p.add_argument("--work", type=Path, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.phase:
        return child_main(args.phase, args.work)

    if not (ROOT / PKG).is_dir():
        print(f"chip_smoke: {PKG}/ is not beside this script; it checks a "
              "checkout, not itself", file=sys.stderr)
        return 1
    # checkpoints are large: the work directory is not under chiprun_out/
    work = ROOT / ".chip_smoke"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir()
    t0 = time.perf_counter()
    try:
        if args.multichip:
            device = run_child("multichip", work)["device"]
        else:
            device = run_child("train", work)["device"]
            run_child("jamba", work)
            run_child("kv_write", work)
            serve_phase(work, size, device)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", flush=True)
        return 1
    finally:
        logs = ROOT / "chiprun_out" / "chip_smoke"
        logs.mkdir(parents=True, exist_ok=True)
        for f in list(work.glob("*.log")) + list(work.glob("*.json")):
            shutil.copy(f, logs / f.name)
        shutil.rmtree(work, ignore_errors=True)
    say("done", f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
