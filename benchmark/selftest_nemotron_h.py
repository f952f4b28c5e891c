"""``selftest.py``'s checks for the cell of the `nemotron_h` family, beside
``selftest_deepseek_v2.py``. Not under ``tests/``; no chip.

    python3 benchmark/selftest_nemotron_h.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_nemotron_h.py --broken     # + the broken timed paths
    python3 benchmark/selftest_nemotron_h.py --control    # + the float8 control, published widths
    python3 benchmark/selftest_nemotron_h.py --witness    # + planted faults, the cell's size (chip)

The rehearsal drives ``serve-nemotron3-super-11l-ep4-agent-turns`` at a
tiny size under ``JAX_PLATFORMS=cpu`` (pattern ``MEM*EME``, 8 Mamba-2
heads of 8 in 2 groups, state 16, sub-chunks of 8, 16 experts of 24 in a
latent of 16 of which the cell holds four), traced and untraced; its lines
carry ``"rehearsal": true`` and no number of them is a device's.
``--broken`` breaks the timed path twice, each time requiring `correct` to
come out false: the admission's state reset taken out (a slot's new
sequence starts from the last one's state), and the held-expert range
ignored (the weights of experts 0-3 read as 4-7's). ``--control`` puts the
reference at float8 in the program's place at the published widths (2 rows
of 96 tokens; some minutes on the CPU) and requires the serving limit to
fail. ``--witness`` holds the serving limit to the faults it is there to
catch at the cell's own widths, depth and vocabulary (the reference with
one fault of ``reference_nemotron_h.FAULTS`` planted takes the program's
place: every head reading group 0's B and C, the gated norm over all 8,192
channels, relu without the square, the router's weights not renormalised,
an eighth of the held experts zeroed, the state dropped at every
sub-chunk's edge; 2 rows of 2,048 tokens; meant for the chip, a minute a
fault), beside the float8 control on the same tokens: each has to fail
through ``check.judge``; ONE held expert of 128 zeroed is read and printed
beside them and NOT required to fail (``TOO_SMALL``: 1/22 of a row's routed
weight on one position in 23, the size of a routing swap, which a bfloat16
program makes by itself: PERF.md section 2); and counts the rows whose 22nd
expert a bfloat16 router would swap. Alone it skips the rehearsal:
``--witness --only``.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import selftest  # noqa: E402
from selftest_afmoe import _rebuilt, witness_gaps  # noqa: E402 (the same
# engine rebuild and the same planted-fault reading as the afmoe cell's)
from selftest_deepseek_v2 import control_gap  # noqa: E402
from lib import check, harness  # noqa: E402

CELL = "serve-nemotron3-super-11l-ep4-agent-turns"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 4, "kv_heads": 1,
              "n_layer": 7, "block_size": 64,
              "hybrid_override_pattern": "MEM*EME", "mamba_num_heads": 8,
              "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
              "chunk_size": 8, "num_experts": 16, "experts_per_token": 4,
              "moe_hidden": 24, "moe_latent_size": 16,
              "moe_shared_hidden": 48, "held_experts": [0, 4],
              "compute_dtype": "float32", "param_dtype": "float32"}
WITNESS_LENGTH = 2048
#: read and reported, not required to fail: see the module docstring
TOO_SMALL = ("held_expert_zeroed",)


def tiny_cell() -> harness.Cell:
    """``selftest.tiny_cell``'s traffic, this family's tiny model."""
    cell = selftest.tiny_cell(CELL)
    cell.config["model"].update(TINY_MODEL)
    # so few slots that every slot serves several requests in a run
    cell.traffic["engine"].update(num_slots=4)
    return cell


def rehearse() -> None:
    for trace in (0, 1):
        out = selftest.drive(tiny_cell(), 2**31 + 42 + trace, 2.0, trace)
        assert out["correct"] is True, f"{CELL} trace={trace}: not correct"
        assert out["failed"] == 0 and out["attempted"] > 0
        assert out["metrics"], f"{CELL} trace={trace}: no metric reported"
        if trace:
            m = out["metrics"]
            assert m["decode_live_state_mb_per_step"]["value"] > 0
            assert m["state_resets_per_iter"]["value"] > 0
            # 4 of 16 experts chosen, 4 held: one a row on average
            per_row = m["nemotron_h_moe_held_assignments_per_row"]["value"]
            assert 0.5 < per_row < 1.5, per_row
            assert 0 < m["nemotron_h_moe_experts_hit_per_step"]["value"] <= 12
            assert m["nemotron_h_moe_expert_load_max_over_mean"]["value"] >= 1.0


def broken() -> None:
    """An engine that does not zero a slot on admission starts a sequence
    from the last one's state; one whose model takes its experts for its
    neighbour's multiplies a token's rows by the wrong weights. Either way
    the served tokens lie far below the reference's best."""
    def no_reset(engine):
        engine._reset_slot_state = lambda slot, iteration: None

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_reset)
    assert out["correct"] is False, "a state that is never reset passed"

    def other_share(engine):
        _rebuilt(engine, engine.cfg.replace(held_experts=(4, 8)))

    out = selftest.drive(tiny_cell(), 10, 2.0, 0, break_engine=other_share)
    assert out["correct"] is False, "experts taken for the next share's passed"


def control() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    model = dict(cell.config["model"], block_size=96)
    gap = control_gap(model, harness.load_reference(cell.config))
    rows = [("served_token_gap", gap,
             cell.config["correct"]["serve"]["token_gap"])]
    assert not check.judge(rows, "nemotron3-super-11l-ep4 float8 control, "
                           "serve"), \
        "the float8 control passed the serving limit"


def router_swaps(model: dict, reference, seed: int = 13,
                 length: int = WITNESS_LENGTH) -> dict:
    """Of ``length`` seeded token embeddings through the first expert
    layer's router: the rows whose set of chosen experts changes when the
    normed hidden state is rounded to bfloat16 first (what a bfloat16
    program's activations do to a ranking of 22 of 512 near-ties), and of
    those the rows where a HELD expert enters or leaves."""
    import jax.numpy as jnp
    import numpy as np

    s = reference.sizes(model)
    params = reference.make_params(seed, model)
    blk = next(b for b in params["blocks"] if "moe" in b)
    toks = np.random.default_rng(seed).integers(0, model["vocab_size"], length)
    x = params["tok_emb"][jnp.asarray(toks)].astype(jnp.float32)
    # the residual stream's scale at an expert layer: a normed row
    h = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + s["eps"])
    pick = lambda v: np.asarray(reference.route(  # noqa: E731
        v, blk["moe"]["router"], s) > 0)
    exact = pick(h)
    rounded = pick(h.astype(jnp.bfloat16).astype(jnp.float32))
    moved = (exact != rounded)
    return {"rows": length, "rows_swapped": int(moved.any(axis=1).sum()),
            "rows_swapped_held": int(
                moved[:, s["lo"]:s["hi"]].any(axis=1).sum())}


def witness() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    limit = cell.config["correct"]["serve"]["token_gap"]
    reference = harness.load_reference(cell.config)
    model = cell.config["model"]
    harness.say(f"witness router_swaps {router_swaps(model, reference)}")
    gaps = witness_gaps(model, reference, length=WITNESS_LENGTH)
    gaps["float8_control"] = control_gap(model, reference, seed=13,
                                         length=WITNESS_LENGTH)
    harness.say(f"witness {gaps}")
    for fault, gap in gaps.items():
        failed = not check.judge([("served_token_gap", gap, limit)],
                                 f"nemotron3-super-11l-ep4 {fault}")
        assert failed or fault in TOO_SMALL, \
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
