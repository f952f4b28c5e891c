"""``selftest.py``'s checks for the cell of the `afmoe` family, beside
``selftest_kimi_linear.py``. Not under ``tests/``; no chip.

    python3 benchmark/selftest_afmoe.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_afmoe.py --broken     # + the broken timed paths
    python3 benchmark/selftest_afmoe.py --control    # + the float8 control, published widths
    python3 benchmark/selftest_afmoe.py --witness    # + planted faults, the cell's size (chip)

The rehearsal drives ``serve-trinity-large-5l-ep16-mixed-len`` at a tiny
size under ``JAX_PLATFORMS=cpu`` (window 8, sliding ring 24, full ring 64,
chunk 16: most prompts roll the sliding rings), traced and untraced; its
lines carry ``"rehearsal": true`` and no number of them is a device's.
``--broken`` breaks the timed path twice, each time requiring `correct` to
come out false: the window ignored (the engine's sliding layers keep a ring
as long as the full layers' and see every earlier position), and the
held-expert range ignored (the program adds the terms of experts it was not
told it holds: the weights of experts 0-3 read as 4-7's). ``--control``
puts the reference at float8 in the program's place at the published widths
(2 rows of 96 tokens; some minutes on the CPU) and requires the serving
limit to fail. ``--witness`` holds the serving limit to the faults it is
there to catch at the cell's own widths, depth, vocabulary and LENGTH (the
reference with one fault planted takes the program's place: the window
ignored on the sliding layers, rotary applied on the full layer, the held
experts taken for their neighbours'; 2 rows of 6,144 tokens, past the
window; meant for the chip, a minute a fault): each has to fail through
``check.judge``. Alone it skips the rehearsal: ``--witness --only``.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import selftest  # noqa: E402
from lib import check, harness  # noqa: E402

CELL = "serve-trinity-large-5l-ep16-mixed-len"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 4, "kv_heads": 2,
              "head_dim": 16, "n_layer": 5, "block_size": 64,
              "ffn_hidden": 96, "sliding_window": 8, "sliding_ring": 24,
              "num_experts": 16, "experts_per_token": 4, "moe_hidden": 32,
              "held_experts": [0, 4], "compute_dtype": "float32",
              "param_dtype": "float32"}


def tiny_cell() -> harness.Cell:
    """``selftest.tiny_cell``'s traffic, this family's tiny model."""
    cell = selftest.tiny_cell(CELL)
    cell.config["model"].update(TINY_MODEL)
    # so few slots that every slot serves several requests in a run
    cell.traffic["engine"].update(num_slots=4)
    return cell


def rehearse() -> None:
    for trace in (0, 1):
        out = selftest.drive(tiny_cell(), 2**31 + 31 + trace, 2.0, trace)
        assert out["correct"] is True, f"{CELL} trace={trace}: not correct"
        assert out["failed"] == 0 and out["attempted"] > 0
        assert out["metrics"], f"{CELL} trace={trace}: no metric reported"
        if trace:
            m = out["metrics"]
            assert m["decode_rows_past_window_pct"]["value"] > 0
            assert m["decode_live_kv_mb_per_step"]["value"] > 0
            per_row = m["afmoe_moe_held_assignments_per_row"]["value"]
            assert 0.4 < per_row < 1.6, per_row  # a quarter of 4 a row, about
            assert m["afmoe_moe_expert_load_max_over_mean"]["value"] >= 1.0


def _rebuilt(engine, cfg) -> None:
    """``engine`` serving ``cfg`` in place of its own: the programs and a
    pool of that configuration's rings."""
    from differential_transformer_replication_tpu.models.decode import (
        init_cache,
    )
    from differential_transformer_replication_tpu.serving.engine import (
        _build_step_fns,
    )

    engine.cfg = cfg
    engine._prefill_fn, engine._decode_fn = _build_step_fns(
        cfg, engine.max_total, lp_k=engine._lp_k,
        quality=engine._quality)[:2]
    engine.cache = init_cache(cfg, engine._rows)


def broken() -> None:
    """An engine whose sliding layers see every earlier position serves
    another model once a sequence passes the window; one whose model takes
    its experts for its neighbour's multiplies a token's rows by the wrong
    weights. Either way the served tokens lie far below the reference's
    best."""
    def no_window(engine):
        size = engine.cfg.block_size
        _rebuilt(engine, engine.cfg.replace(sliding_window=size,
                                            sliding_ring=size))

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_window)
    assert out["correct"] is False, "sliding layers that see everything passed"

    def other_share(engine):
        _rebuilt(engine, engine.cfg.replace(held_experts=(4, 8)))

    out = selftest.drive(tiny_cell(), 10, 2.0, 0, break_engine=other_share)
    assert out["correct"] is False, "experts taken for the next share's passed"


def control() -> None:
    import jax.numpy as jnp
    import numpy as np

    cell = harness.find_cell(harness.load_benchmark(), CELL)
    reference = harness.load_reference(cell.config)
    model = dict(cell.config["model"], block_size=96)
    params = reference.make_params(11, model)
    toks = jnp.asarray(np.random.default_rng(11).integers(
        0, model["vocab_size"], (2, 97)))
    gaps = np.asarray(reference.make_token_gaps(model, "fp8")(
        params, toks[:, :-1], toks[:, 1:]))
    rows = [("served_token_gap", float(gaps.max()),
             cell.config["correct"]["serve"]["token_gap"])]
    assert not check.judge(rows, "trinity-large-5l-ep16 float8 control, serve"), \
        "the float8 control passed the serving limit"


def witness_gaps(model: dict, reference, seed: int = 13, rows: int = 2,
                 length: int = 6144) -> dict:
    """``served_token_gap`` of the reference with each fault of
    ``reference.FAULTS`` planted (the token IT puts first at a position,
    judged as a served token is) on seeded tokens of ``length`` positions,
    past the window."""
    import jax.numpy as jnp
    import numpy as np

    params = reference.make_params(seed, model)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], (rows, length + 1)))
    return {fault: float(np.asarray(reference.make_token_gaps(
        model, fault=fault)(params, toks[:, :-1], toks[:, 1:])).max())
        for fault in reference.FAULTS[1:]}


def witness() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    limit = cell.config["correct"]["serve"]["token_gap"]
    gaps = witness_gaps(cell.config["model"],
                        harness.load_reference(cell.config))
    harness.say(f"witness {gaps}")
    for fault, gap in gaps.items():
        assert not check.judge([("served_token_gap", gap, limit)],
                               f"trinity-large-5l-ep16 {fault}"), \
            f"{fault} passed the serving limit"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--broken", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--only", action="store_true",
                    help="skip the rehearsal (a chip has no CPU cell)")
    args = ap.parse_args()
    if not args.only:
        rehearse()
        print("ok rehearse")
    for flag, fn in (("broken", broken), ("control", control),
                     ("witness", witness)):
        if getattr(args, flag):
            fn()
            print(f"ok {flag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
