"""``selftest.py``'s checks for the cell of the `deepseek_v2` family, beside
``selftest_afmoe.py``. Not under ``tests/``; no chip.

    python3 benchmark/selftest_deepseek_v2.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_deepseek_v2.py --broken     # + the broken timed paths
    python3 benchmark/selftest_deepseek_v2.py --control    # + the float8 control, published widths
    python3 benchmark/selftest_deepseek_v2.py --witness    # + planted faults, the cell's size (chip)

The rehearsal drives ``serve-deepseek-v2-5l-ep8-code-chat`` at a tiny size
under ``JAX_PLATFORMS=cpu`` (a ring of 64 latents, a YaRN block that scales
from 16 positions, 16 experts in 4 groups of which the cell holds one),
traced and untraced; its lines carry ``"rehearsal": true`` and no number of
them is a device's. ``--broken`` breaks the timed path twice, each time
requiring `correct` to come out false: the rotation's positions ignored
(every row is turned as if it stood at position 0), and the held-expert
range ignored (the weights of group 0 read as group 1's). ``--control``
puts the reference at float8 in the program's place at the published widths
(2 rows of 96 tokens; some minutes on the CPU) and requires the serving
limit to fail. ``--witness`` holds the serving limit to the faults it is
there to catch at the cell's own widths, depth, vocabulary and LENGTH (the
reference with one fault of ``reference_deepseek_v2.FAULTS`` planted takes
the program's place: a router that renormalises, a key part not rotated,
``m^2`` left out of the scale, one held expert zeroed; 2 rows of 6,144
tokens, past the YaRN block's 4,096; meant for the chip, a minute a fault),
beside the float8 control on the same tokens: each has to fail through
``check.judge``. Alone it skips the rehearsal: ``--witness --only``.
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
from lib import check, harness  # noqa: E402

CELL = "serve-deepseek-v2-5l-ep8-code-chat"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 4, "n_layer": 3,
              "block_size": 64, "ffn_hidden": 96, "q_lora_rank": 24,
              "kv_lora_rank": 16, "qk_nope_head_dim": 8,
              "qk_rope_head_dim": 8, "v_head_dim": 8, "rope_theta": 100.0,
              "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                               "beta_slow": 1, "mscale": 0.707,
                               "mscale_all_dim": 0.707,
                               "original_max_position_embeddings": 16},
              "num_experts": 16, "experts_per_token": 3, "moe_hidden": 32,
              "n_group": 4, "topk_group": 2, "held_experts": [0, 4],
              "compute_dtype": "float32",
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
        out = selftest.drive(tiny_cell(), 2**31 + 41 + trace, 2.0, trace)
        assert out["correct"] is True, f"{CELL} trace={trace}: not correct"
        assert out["failed"] == 0 and out["attempted"] > 0
        assert out["metrics"], f"{CELL} trace={trace}: no metric reported"
        if trace:
            m = out["metrics"]
            assert m["decode_live_latent_mb_per_step"]["value"] > 0
            # 2 of 4 groups kept: half the rows reach the held group and
            # find 1.5 of their 3 experts there
            assert 25 < m["dsv2_moe_rows_in_held_group_pct"]["value"] < 75
            per_row = m["dsv2_moe_held_assignments_per_row"]["value"]
            assert 0.3 < per_row < 1.3, per_row
            assert m["dsv2_moe_expert_load_max_over_mean"]["value"] >= 1.0


def broken() -> None:
    """An engine that turns nothing serves a model without positions; one
    whose model takes its experts for the next group's multiplies a
    token's rows by the wrong weights. Either way the served tokens lie
    far below the reference's best."""
    def no_positions(engine):
        # theta 1e30: every frequency but the first vanishes, and YaRN's
        # blend slows that one: the rotation is as good as left out
        _rebuilt(engine, engine.cfg.replace(rope_theta=1e30))

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_positions)
    assert out["correct"] is False, "a rotation that turns nothing passed"

    def other_share(engine):
        _rebuilt(engine, engine.cfg.replace(held_experts=(4, 8)))

    out = selftest.drive(tiny_cell(), 10, 2.0, 0, break_engine=other_share)
    assert out["correct"] is False, "experts taken for the next group's passed"


def control_gap(model: dict, reference, seed: int = 11, rows: int = 2,
                length: int = 96) -> float:
    """``served_token_gap`` of the float8 control on seeded tokens."""
    import jax.numpy as jnp
    import numpy as np

    params = reference.make_params(seed, model)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], (rows, length + 1)))
    return float(np.asarray(reference.make_token_gaps(model, "fp8")(
        params, toks[:, :-1], toks[:, 1:])).max())


def control() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    model = dict(cell.config["model"], block_size=96)
    gap = control_gap(model, harness.load_reference(cell.config))
    rows = [("served_token_gap", gap,
             cell.config["correct"]["serve"]["token_gap"])]
    assert not check.judge(rows, "deepseek-v2-5l-ep8 float8 control, serve"), \
        "the float8 control passed the serving limit"


def witness() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    limit = cell.config["correct"]["serve"]["token_gap"]
    reference = harness.load_reference(cell.config)
    gaps = witness_gaps(cell.config["model"], reference)
    gaps["float8_control"] = control_gap(cell.config["model"], reference,
                                         seed=13, length=6144)
    harness.say(f"witness {gaps}")
    for fault, gap in gaps.items():
        assert not check.judge([("served_token_gap", gap, limit)],
                               f"deepseek-v2-5l-ep8 {fault}"), \
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
