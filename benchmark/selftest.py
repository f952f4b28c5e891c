"""The benchmark's own checks. Not under ``tests/``: it changes no test
count, and it is the only place where the harness runs without a chip.

    python3 benchmark/selftest.py              # arithmetic: fixture, plans
    python3 benchmark/selftest.py --rehearse   # + cells end to end, tiny, CPU
    python3 benchmark/selftest.py --control    # + the lower-precision control
    python3 benchmark/selftest.py --broken     # + the broken timed paths

``--rehearse`` skips the harness's look for a chip and drives the rest of a
run (one train cell, one serve cell, traced and untraced) at a tiny size
under ``JAX_PLATFORMS=cpu``; its result lines carry ``"rehearsal": true``
and the CPU's name as the device, and no number of them is a device's. It
also serves a configuration that states no ``train`` block and a
``ModelConfig`` field the shipped files do not (the key has to arrive in
the engine), and requires one with a misspelt key to exit naming it.
``--control`` puts the reference at float8 in the program's place and
requires `correct`'s numbers to fail; ``--broken`` breaks the timed path
underneath (a step that returns its state unchanged; a decode step whose
logits are shifted by one token) and requires ``correct`` to come out
false. ``run.py`` itself never runs without a TPU.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import check, harness, stats, traffic, xplane  # noqa: E402

FIXTURE = os.path.join(HERE, "lib", "fixture.xplane.pb")

# -- a hand-made trace ------------------------------------------------------
# Two chips. Chip 0's "XLA Ops" (microseconds from the line's start):
#   fusion.1 0-10 (it READS all-reduce.1 and is no collective),
#   _tm_fwd_kernel 10-40, all-reduce.1 35-55 (overlaps the kernel for 5),
#   jvp__.3 70-90 (a Pallas kernel without a name), _dattn_fwd_kernel 90-100
# so busy = 55 + 30 = 85 of a 100 us window (idle 15%); the collective is
# exposed for 15; flash 30, decode_attention 10 (it ends in the flash
# needle and must not be counted there), all Pallas kernels 60. Chip 1 runs one op
# 0-50. Host: "bench:data" 50-75 covers the idle gap 55-70.
_CC = ', custom_call_target="tpu_custom_call"'
_OPS0 = [("%fusion.1 = f32[8] fusion(f32[8] %all-reduce.1)", 0, 10),
         ("%_tm_fwd_kernel.2 = bf16[8] custom-call(bf16[8] %p)" + _CC, 10, 30),
         ("%all-reduce.1 = f32[8] all-reduce(f32[8] %fusion.0)", 35, 20),
         ("%jvp__.3 = bf16[8] custom-call(bf16[8] %p)" + _CC, 70, 20),
         ("%_dattn_fwd_kernel.4 = bf16[8] custom-call(bf16[8] %p)" + _CC, 90, 10)]
_OPS1 = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 50)]
_MODS0 = [("jit__decode(123)", 70, 30)]
_HOST = [("bench:data", 50, 25), ("other", 0, 100)]


def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _ld(field: int, payload: bytes) -> bytes:
    return _vint(field << 3 | 2) + _vint(len(payload)) + payload


def _vi(field: int, n: int) -> bytes:
    return _vint(field << 3) + _vint(n)


def _plane(name: str, lines) -> bytes:
    ids, meta, body = {}, b"", b""
    for lname, events in lines:
        evs = b""
        for ename, start_us, dur_us in events:
            mid = ids.setdefault(ename, len(ids) + 1)
            evs += _ld(4, _vi(1, mid) + _vi(2, start_us * 10**6)
                       + _vi(3, dur_us * 10**6))
        body += _ld(3, _ld(2, lname.encode()) + _vi(3, 1000) + evs)
    for ename, mid in ids.items():
        meta += _ld(4, _vi(1, mid) + _ld(2, _vi(1, mid) + _ld(2, ename.encode())))
    return _ld(2, name.encode()) + body + meta


def fixture_bytes() -> bytes:
    return (_ld(1, _plane("/device:TPU:0", [("XLA Ops", _OPS0),
                                            ("XLA Modules", _MODS0)]))
            + _ld(1, _plane("/device:TPU:1", [("XLA Ops", _OPS1)]))
            + _ld(1, _plane("/host:CPU", [("thread-1", _HOST)])))


def close(a: float, b: float, what: str) -> None:
    if abs(a - b) > 1e-9 * max(1.0, abs(b)):
        raise AssertionError(f"{what}: got {a!r}, want {b!r}")


def test_fixture() -> None:
    with open(FIXTURE, "rb") as f:
        data = f.read()
    assert data == fixture_bytes(), "lib/fixture.xplane.pb is not the recorded fixture"
    planes = xplane.parse_xspace(data)
    assert [p.name for p in xplane.device_planes(planes)] == [
        "/device:TPU:0", "/device:TPU:1"]
    busy, window = xplane.busy_and_window(planes)
    close(window, 100e-6, "window")
    close(busy, (85e-6 + 50e-6) / 2, "busy, mean of the chips")
    one = [planes[0]]
    busy0, _ = xplane.busy_and_window(one)
    close(100 * (1 - busy0 / 100e-6), 15.0, "idle share of chip 0")
    close(xplane.bucket_seconds(one, "flash_attention")[0], 30e-6, "flash")
    close(xplane.bucket_seconds(one, "pallas")[0], 60e-6, "all Pallas")
    close(xplane.needle_seconds(one, ["all-reduce"])[0], 20e-6,
          "a collective's reader is no collective")
    close(xplane.bucket_seconds(one, "decode_attention")[0], 10e-6, "decode")
    close(xplane.exposed_seconds(one, ["all-reduce"]), 15e-6, "exposed")
    close(xplane.needle_seconds(one, ["jit__decode"], xplane.MODULES_LINE)[0],
          30e-6, "decode program")
    gaps = dict(xplane.idle_gaps_by_host_span(planes, "bench:"))
    # 55-70 is one gap of 15 us, under the 20 us seam threshold
    close(gaps["seams-under-20us"], 15e-6, "seams")
    old, xplane.SEAM_S = xplane.SEAM_S, 1e-6
    try:
        gaps = dict(xplane.idle_gaps_by_host_span(planes, "bench:"))
    finally:
        xplane.SEAM_S = old
    close(gaps["data"], 15e-6, "gap named by the host span")
    summary = xplane.summary(planes, "bench:")
    close(summary["window_s"], 100e-6, "the summary's window")
    ops = dict(summary["breakdown"]["device_ops"])
    close(ops["flash_attention"], 30e-6, "breakdown bucket")
    close(ops["fusion"], 10e-6, "breakdown op group")
    close(ops["pallas:jvp__"], 20e-6, "breakdown unnamed kernel")
    # the readers, declared and .py, over the same trace (one traced step)
    cell = harness.find_cell(harness.load_benchmark(), cells_by_kind()["open_loop"])
    run = harness.Run(cell, harness.Env([], {"bf16_flops_per_s": 197e12,
                                             "hbm_bytes_per_s": 819e9}),
                      planes=planes)
    run.values.update(trace_steps=1, decode_rows=2.0, decode_live_positions=100.0)
    read = lambda name: harness._reader_for(name)(run)  # noqa: E731
    close(read("decode_step_device_ms"), 30e-3, "decode program, ms")
    close(read("device_idle_pct.serve"), 100 * (1 - 67.5 / 100), "idle, chips' mean")
    assert read("decode_step_roofline") > 0  # a 30 us fixture step: no real share


def test_intervals() -> None:
    m = xplane.merge([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert m == [(0, 3), (5, 6)], m
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    close(stats.percentile([1, 2, 3, 4], 50), 2.5, "median")
    close(stats.percentile(list(range(101)), 95), 95.0, "p95")
    close(check.leaf_gap([1.0, 0.0, 2.2], [1.0, 1e-9, 2.0]), 0.1, "leaf gap")


def test_plan() -> None:
    mix = harness.load_json("traffic", "chat-open-0.8knee.json")
    a = traffic.open_loop_plan(mix, 3, 10.0, 12000)
    b = traffic.open_loop_plan(mix, 2**31 + 11, 10.0, 12000)
    again = traffic.open_loop_plan(mix, 3, 10.0, 12000)
    assert [p.prompt for p in a] == [p.prompt for p in again]
    assert [p.prompt for p in a] != [p.prompt for p in b]
    sizes = lambda plan: sorted((len(p.prompt), p.max_new_tokens) for p in plan)  # noqa: E731
    assert sizes(a) == sizes(b), "every seed gets the same set of sizes"
    assert all(len(p.prompt) + p.max_new_tokens <= mix["max_total"] for p in a)
    ramp, rate = mix["arrival"]["ramp_s"], mix["arrival"]["rate_per_s"]
    inside = lambda plan: sorted(  # noqa: E731
        (len(p.prompt), p.max_new_tokens) for p in plan
        if ramp <= p.due_s < ramp + 10.0)
    assert len(inside(a)) == round(rate * 10.0), "the window's count is fixed"
    assert inside(a) == inside(b), "every seed gets the same window"
    close(a[-1].due_s, ramp + 10.0, "the last request closes the window")
    # the parameters no mix uses yet (the prefill and sessions cells to come)
    later = dict(mix, prompt_len={"dist": "uniform", "min": 300, "max": 340},
                 output_len={"dist": "uniform", "min": 4, "max": 16},
                 shared_prefix={"len": 256, "count": 2})
    c = traffic.open_loop_plan(later, 3, 10.0, 12000)
    assert all(300 <= len(p.prompt) <= 340 and 4 <= p.max_new_tokens <= 16
               for p in c)
    assert len({tuple(p.prompt[:256]) for p in c}) == 2, "two shared prefixes"
    assert len({tuple(p.prompt[256:]) for p in c}) == len(c), "distinct tails"


# -- the cells end to end, tiny, without a chip ------------------------------

TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 2, "n_layer": 2,
              "block_size": 64, "compute_dtype": "float32",
              "attention_impl": "xla", "ffn_impl": "xla"}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.find_cell(harness.load_benchmark(), name)
    cell = copy.deepcopy(cell)
    cell.config["model"].update(TINY_MODEL)
    t = cell.traffic
    if t["kind"] == "train_steps":
        t.update(rows_per_chip=4, corpus_tokens=8192, steps_per_window=3,
                 check={"steps": 3, "rows_per_block": 2})
    else:
        t["arrival"].update(rate_per_s=30.0, ramp_s=1.0)
        t["prompt_len"].update(median=12, min=4, max=40)
        t["output_len"].update(median=6, min=2, max=16)
        t.update(max_total=64, drain_s=20.0, trace_seconds=0.5)
        t["engine"].update(num_slots=8, prefill_chunk=16, prefill_budget=64)
    return cell


def rehearsal_env(chips: int) -> harness.Env:
    import jax

    return harness.Env(jax.devices()[:chips], None, rehearsal=True)


def drive(cell: harness.Cell, seed: int, seconds: float, trace: int,
          **hooks) -> dict:
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    line = harness.driver(cell.traffic["kind"]).run(
        cell, rehearsal_env(cell.chips), args, harness.process_start(),
        **hooks)
    out = json.loads(line)
    out["rehearsal"] = True
    print(json.dumps(out))
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in out, f"the result line lacks {key!r}"
    return out


def cells_by_kind() -> dict:
    bench = harness.load_benchmark()
    kinds = {}
    for w in bench["workloads"]:
        kind = harness.load_json("traffic", w["traffic"] + ".json")["kind"]
        if w["chips"] == 1:
            kinds.setdefault(kind, w["name"])
    return kinds


def rehearse() -> None:
    for kind, name in cells_by_kind().items():
        for trace in (0, 1):
            out = drive(tiny_cell(name), 2**31 + 17 + trace, 2.0, trace)
            assert out["correct"] is True, f"{name} trace={trace}: not correct"
            assert out["failed"] == 0 and out["attempted"] > 0
            assert out["metrics"], f"{name} trace={trace}: no metric reported"
    if "open_loop" in cells_by_kind():
        rehearse_serve_only()


def serve_only_cell(**model_keys) -> harness.Cell:
    """A configuration that can only be served, made as ``tiny_cell``
    makes its cells: the shipped file in memory, ``TINY_MODEL`` over it,
    the ``train`` block and its limits taken out, ``model_keys`` stated."""
    cell = tiny_cell(cells_by_kind()["open_loop"])
    del cell.config["train"]
    cell.config["correct"].pop("train", None)
    cell.config["model"].update(model_keys)
    return cell


def refused(cell: harness.Cell, word: str) -> None:
    """The run of ``cell`` has to exit with a sentence that holds ``word``."""
    try:
        drive(cell, 7, 1.0, 0)
    except SystemExit as e:
        assert word in str(e), f"the exit does not name {word}: {e}"
        print(f"refused, as it has to be: {e}")
    else:
        raise AssertionError(f"a configuration that had to exit on {word} ran")


def rehearse_serve_only() -> None:
    """The ``model`` block reaches the program whole, and a configuration
    states the paths it has."""
    import jax.numpy as jnp

    seen = {}

    def look(engine):
        seen["k"] = engine.cache[0]["k"].dtype

    # a ModelConfig field that no shipped file states: it has to arrive
    out = drive(serve_only_cell(kv_cache_dtype="int8"), 2**31 + 23, 2.0, 0,
                break_engine=look)
    assert seen["k"] == jnp.int8, f"kv_cache_dtype was dropped: {seen['k']}"
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    refused(serve_only_cell(kv_cache_dtpye="int8"), "kv_cache_dtpye")
    # the train driver, asked for a path the configuration does not state
    cell = tiny_cell(cells_by_kind()["train_steps"])
    del cell.config["train"]
    refused(cell, "`train`")


def broken() -> None:
    """`correct` has to come out false when the timed path is broken
    underneath, whatever the limits."""
    import jax
    import jax.numpy as jnp

    kinds = cells_by_kind()

    def stuck(step):
        def same_state(state, batch, rng):
            _, metrics = step(jax.tree_util.tree_map(jnp.copy, state),
                              batch, rng)
            return state, metrics
        return same_state

    out = drive(tiny_cell(kinds["train_steps"]), 5, 1.0, 0, break_step=stuck)
    assert out["correct"] is False, "a step that leaves its state unchanged passed"

    def shifted(engine):
        real = engine._decode_fn

        def decode(*a):
            logits, cache = real(*a)
            return jnp.roll(logits, 1, axis=-1), cache
        decode._cache_size = real._cache_size  # compile_stats() reads it
        engine._decode_fn = decode

    if "open_loop" in kinds:
        out = drive(tiny_cell(kinds["open_loop"]), 6, 2.0, 0,
                    break_engine=shifted)
        assert out["correct"] is False, "shifted decode logits passed"


def control() -> None:
    """The reference at float8 in the program's place, at a size a test
    run holds (the recipe's widths and depth, 4 rows of 128 tokens): at
    least one of `correct`'s numbers has to fail its limit, for training
    and for serving. The full-size readings are in PERF.md."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bench = harness.load_benchmark()
    for cfg_entry in bench["configs"]:
        with open(os.path.join(harness.ROOT, cfg_entry["file"])) as f:
            config = json.load(f)
        reference = harness.load_reference(config)
        model = dict(config["model"], block_size=128)
        params = reference.make_params(11, model)
        rng = np.random.default_rng(11)
        toks = jnp.asarray(rng.integers(0, model["vocab_size"], (3, 4, 129)))
        x, y = toks[..., :-1], toks[..., 1:]
        limits = config["correct"]  # a configuration states the paths it has
        if "train" in limits:
            readings = {}
            for quant in (None, "fp8"):
                got = jax.device_get(reference.make_train_steps(
                    model, config["train"], 4, quant)(params, x, y))
                readings[quant] = {
                    "losses": [float(v) for v in got["losses"]],
                    "first_grad_norms": [float(v) for v in jax.tree_util.tree_leaves(got["first_grad_norms"])],
                    "delta_norms": [float(v) for v in jax.tree_util.tree_leaves(got["delta_norms"])]}
            rows = check.train_rows(readings["fp8"], readings[None],
                                    limits["train"])
            assert not check.judge(rows, cfg_entry["name"] + " float8 control, train"), \
                "the float8 control passed the training limits"
        if "serve" in limits:
            gaps = np.asarray(reference.make_token_gaps(model, "fp8")(
                params, x[0], y[0]))
            rows = [("served_token_gap", float(gaps.max()),
                     limits["serve"]["token_gap"])]
            assert not check.judge(rows, cfg_entry["name"] + " float8 control, serve"), \
                "the float8 control passed the serving limit"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--broken", action="store_true")
    ap.add_argument("--write-fixture", action="store_true",
                    help="(re)write lib/fixture.xplane.pb from the table above")
    args = ap.parse_args()
    if args.write_fixture:
        with open(FIXTURE, "wb") as f:
            f.write(fixture_bytes())
    for test in (test_intervals, test_fixture, test_plan):
        test()
        print(f"ok {test.__name__}")
    for flag, fn in (("rehearse", rehearse), ("control", control),
                     ("broken", broken)):
        if getattr(args, flag):
            fn()
            print(f"ok {flag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
