"""``selftest.py``'s checks for the cell of the `kimi_linear` family, beside
``selftest_jamba.py``. Not under ``tests/``; no chip.

    python3 benchmark/selftest_kimi_linear.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_kimi_linear.py --broken     # + the broken timed paths
    python3 benchmark/selftest_kimi_linear.py --control    # + the float8 control, published widths
    python3 benchmark/selftest_kimi_linear.py --witness    # + planted expert faults, published widths

The rehearsal drives ``serve-kimi-linear-5l-ep2-doc-chat`` at a tiny size
under ``JAX_PLATFORMS=cpu`` (the update kernel in interpret mode), traced
and untraced; its lines carry ``"rehearsal": true`` and no number of them
is a device's. ``--broken`` breaks the timed path twice, each time
requiring `correct` to come out false: the admission reset taken out of the
engine (a slot keeps the KDA state its last sequence left), and the
held-expert range ignored (the program adds the terms of experts it was
not told it holds: the weights of the first half read as the second's).
``--control`` puts the reference at float8 in the program's place at the
published widths (one KDA + experts layer and the MLA layer's widths are
all there at depth 5; 2 rows of 96 tokens; some minutes and 30 GB of host
memory on the CPU) and requires the serving limit to fail.
``--witness`` holds the serving limit to the faults of the expert path it
is there to catch, at the cell's own widths, depth and vocabulary (4 rows
of 128 tokens through the program's full forward; meant for the chip,
where it takes a minute; on the CPU the grouped product runs in interpret
mode, some tens of minutes): the program as it is has to pass, and the
program with the routed experts adding nothing, and with its experts taken
for the other half's, each have to fail through ``check.judge``. It then
reads what sets the program's own gap: the reference with ONLY the
router's product rounded to bfloat16, and both again with the routed
experts' down projection at the full fan-in rule (``assumed`` draws it at
a third). Alone it skips the rehearsal: ``--witness --only``.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import selftest  # noqa: E402
from lib import check, harness  # noqa: E402

CELL = "serve-kimi-linear-5l-ep2-doc-chat"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 2, "n_layer": 5,
              "block_size": 64, "ffn_hidden": 96, "kda_head_dim": 16,
              "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 16,
              "experts_per_token": 4, "moe_hidden": 32,
              "held_experts": [0, 8], "compute_dtype": "float32",
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
        out = selftest.drive(tiny_cell(), 2**31 + 29 + trace, 2.0, trace)
        assert out["correct"] is True, f"{CELL} trace={trace}: not correct"
        assert out["failed"] == 0 and out["attempted"] > 0
        assert out["metrics"], f"{CELL} trace={trace}: no metric reported"
        if trace:
            assert out["metrics"]["state_resets_per_iter"]["value"] > 0
            per_row = out["metrics"]["moe_held_assignments_per_row"]["value"]
            assert 1.0 < per_row < 3.0, per_row  # half of 4 a row, about
            assert out["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1


def broken() -> None:
    """A slot that is not zeroed on admission hands its last sequence's
    KDA state to the next one; an engine whose model takes its experts
    for another half's multiplies a token's rows by the wrong weights.
    Either way the served tokens lie far below the reference's best."""
    def no_reset(engine):
        engine._reset_slot_state = lambda slot, iteration: None

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_reset)
    assert out["correct"] is False, "a slot pool that is never reset passed"

    def other_half(engine):
        from differential_transformer_replication_tpu.serving.engine import (
            _build_step_fns,
        )

        engine.cfg = engine.cfg.replace(held_experts=(8, 16))
        engine._prefill_fn, engine._decode_fn = _build_step_fns(
            engine.cfg, engine.max_total, lp_k=engine._lp_k,
            quality=engine._quality)[:2]

    out = selftest.drive(tiny_cell(), 10, 2.0, 0, break_engine=other_half)
    assert out["correct"] is False, "experts taken for the other half passed"


def control() -> None:
    import jax.numpy as jnp
    import numpy as np

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, CELL)
    reference = harness.load_reference(cell.config)
    model = dict(cell.config["model"], block_size=96)
    params = reference.make_params(11, model)
    toks = jnp.asarray(np.random.default_rng(11).integers(
        0, model["vocab_size"], (2, 97)))
    gaps = np.asarray(reference.make_token_gaps(model, "fp8")(
        params, toks[:, :-1], toks[:, 1:]))
    rows = [("served_token_gap", float(gaps.max()),
             cell.config["correct"]["serve"]["token_gap"])]
    assert not check.judge(rows, "kimi-linear-5l-ep2 float8 control, serve"), \
        "the float8 control passed the serving limit"


def witness_gaps(config: dict, model: dict, seed: int = 13, rows: int = 4,
                 length: int = 128, down_scale: float = 1.0) -> dict:
    """``served_token_gap`` of the program's full forward on seeded tokens
    (the token it puts first at a position, judged as a served token is),
    as it is and with each fault planted; and of the reference with only
    the router's product rounded. ``model`` is the configuration's block
    or a smaller one; ``down_scale`` multiplies the routed experts' down
    projection (3 undoes ``assumed``'s third) for program and reference
    alike."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from differential_transformer_replication_tpu.models import model_forward
    from lib import program

    config = dict(config, model=dict(model, block_size=length))
    model = config["model"]
    reference = harness.load_reference(config)
    cfg = program.served_model(config)
    params = reference.make_params(seed, model)
    if down_scale != 1.0:
        for blk in params["blocks"]:
            if "moe" in blk:
                down = blk["moe"]["experts"]["down"]
                blk["moe"]["experts"]["down"] = (
                    down.astype(jnp.float32) * down_scale).astype(down.dtype)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], (rows, length + 1)))
    seqs = toks[:, :-1]

    def served_by(cfg, params):
        logits = jax.jit(lambda p: model_forward(p, seqs, cfg)[0])(params)
        return jnp.argmax(logits.astype(jnp.float32), axis=-1)

    lo, hi = cfg.held_expert_range
    silent = dict(params, blocks=[
        dict(blk, moe=dict(blk["moe"], experts=dict(
            blk["moe"]["experts"],
            down=jnp.zeros_like(blk["moe"]["experts"]["down"]))))
        if "moe" in blk else blk for blk in params["blocks"]])
    served = {
        "program": served_by(cfg, params),
        "routed_part_left_out": served_by(cfg, silent),
        # the weights it holds read as the experts hi .. 2 hi - lo
        "other_half_s_range": served_by(
            cfg.replace(held_experts=(hi, 2 * hi - lo)), params),
    }
    del silent
    judged = reference.make_token_gaps(model)
    out = {name: float(np.asarray(judged(params, seqs, tok)).max())
           for name, tok in served.items()}
    out["reference_router_bf16"] = float(np.asarray(reference.make_token_gaps(
        model, reference.ROUTER_BF16)(params, seqs, toks[:, 1:])).max())
    return out


def witness() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    config, limit = cell.config, cell.config["correct"]["serve"]["token_gap"]
    gaps = witness_gaps(config, config["model"])
    harness.say(f"witness {gaps}")
    for name in ("routed_part_left_out", "other_half_s_range"):
        assert not check.judge([("served_token_gap", gaps[name], limit)],
                               f"kimi-linear-5l-ep2 {name}"), \
            f"{name} passed the serving limit"
    assert check.judge([("served_token_gap", gaps["program"], limit)],
                       "kimi-linear-5l-ep2 the program, full forward")
    # what sets the program's gap: the same readings with the routed
    # experts' down projection as the fan-in rule draws it
    harness.say(f"witness down x3 {witness_gaps(config, config['model'], down_scale=3.0)}")


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
