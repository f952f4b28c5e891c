"""``selftest.py``'s checks for the cell of the `lfm2` family, beside
``selftest_nemotron_h.py``. Not under ``tests/``; no chip.

    python3 benchmark/selftest_lfm2.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_lfm2.py --broken     # + the broken timed paths
    python3 benchmark/selftest_lfm2.py --control    # + the float8 control, published widths
    python3 benchmark/selftest_lfm2.py --witness    # + planted faults, the cell's size (chip)

The rehearsal drives ``serve-lfm2-24b-a2b-5l-extract-rag`` at a tiny size
under ``JAX_PLATFORMS=cpu`` (layers ``conv full_attention conv conv conv``,
hidden 64, 8 query heads of 64 on 2 K/V heads, 16 experts of 24 of which a
token keeps 4 and the cell holds all), traced and untraced; its lines carry
``"rehearsal": true`` and no number of them is a device's. ``--broken``
breaks the timed path twice, each time requiring `correct` to come out
false: the admission's window reset taken out (a slot's new sequence reads
the last one's two gated inputs), and the taps' order reversed (the weights'
``conv_w`` flipped along the taps under the engine). ``--control`` puts the
reference at float8 in the program's place at the published widths (2 rows
of 96 tokens; some minutes on the CPU) and requires the serving limit to
fail. ``--witness`` holds the serving limit to the faults it is there to
catch at the cell's own widths, depth and vocabulary (the reference with one
fault of ``reference_lfm2.FAULTS`` planted takes the program's place: the
window dropped at a chunk's edge, the taps reversed, the rotation before the
head norm, q and k not normed, the weights not renormalised; 2 rows of 2,048
tokens; meant for the chip, five seconds a fault), beside the float8 control
on the same tokens: each has to fail through ``check.judge``. Three more are
read and printed beside them and NOT required to fail (``TOO_SMALL``; PERF.md
section 2 has the readings): ONE expert of 64 zeroed and an EIGHTH of them
zeroed (a quarter of a row's routed weight gone on one position in 16, or on
two in five: the term is smaller than the one a routing SWAP exchanges, which
a bfloat16 program makes by itself a few times in a thousand rows, and the
program's own largest reading comes from such a swap: no limit stands between
the two), and the router's bias added to the weights and not only to the
ranking (0.02 on scores of 0.9 under a renormalisation: 2% of a weight; a bias
wide enough to show there would decide the ranking alone). It also counts the
rows whose 4th expert a bfloat16 router would swap. Alone it skips the
rehearsal: ``--witness --only``.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import selftest  # noqa: E402
from selftest_afmoe import witness_gaps  # noqa: E402 (the same planted-
# fault reading as the afmoe cell's)
from selftest_deepseek_v2 import control_gap  # noqa: E402
from selftest_nemotron_h import router_swaps  # noqa: E402
from lib import check, harness  # noqa: E402

CELL = "serve-lfm2-24b-a2b-5l-extract-rag"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 8, "kv_heads": 2,
              "head_dim": 64, "n_layer": 5, "block_size": 64,
              "ffn_hidden": 96, "num_experts": 16, "experts_per_token": 4,
              "moe_hidden": 24, "held_experts": [0, 16],
              "compute_dtype": "float32", "param_dtype": "float32"}
WITNESS_LENGTH = 2048
#: read and reported, not required to fail: see the module docstring
TOO_SMALL = ("one_expert_zeroed", "eighth_of_experts_zeroed",
             "bias_in_weights")


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
            assert m["state_resets_per_iter"]["value"] > 0
            # 4 of 16 experts a row and layer, all held: at most 4 x 16 hit
            assert 0 < m["lfm2_moe_experts_hit_per_step"]["value"] <= 64
            assert m["lfm2_moe_expert_load_max_over_mean"]["value"] >= 1.0


def broken() -> None:
    """An engine that does not zero a slot on admission starts a sequence
    with the last one's window; one whose taps run the other way weighs
    every token's two predecessors wrongly. Either way the served tokens
    lie far below the reference's best."""
    def no_reset(engine):
        engine._reset_slot_state = lambda slot, iteration: None

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_reset)
    assert out["correct"] is False, "a window that is never reset passed"

    def taps_reversed(engine):
        engine.params = dict(engine.params, blocks=[
            dict(blk, conv=dict(blk["conv"], conv_w=blk["conv"]["conv_w"][::-1]))
            if "conv" in blk else blk for blk in engine.params["blocks"]])

    out = selftest.drive(tiny_cell(), 10, 2.0, 0, break_engine=taps_reversed)
    assert out["correct"] is False, "taps applied in reversed order passed"


def control() -> None:
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    model = dict(cell.config["model"], block_size=96)
    gap = control_gap(model, harness.load_reference(cell.config))
    rows = [("served_token_gap", gap,
             cell.config["correct"]["serve"]["token_gap"])]
    assert not check.judge(rows, "lfm2-24b-a2b-5l float8 control, serve"), \
        "the float8 control passed the serving limit"


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
                                 f"lfm2-24b-a2b-5l {fault}")
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
