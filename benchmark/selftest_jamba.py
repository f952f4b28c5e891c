"""``selftest.py``'s checks for the cells of the `jamba` family, beside it
(that file serves the first cell of a kind, which is a `diff` cell). Not
under ``tests/``; no chip.

    python3 benchmark/selftest_jamba.py              # the cell end to end, tiny, CPU
    python3 benchmark/selftest_jamba.py --broken     # + the broken timed path
    python3 benchmark/selftest_jamba.py --control    # + the float8 control, published widths

The rehearsal drives ``serve-jamba2-3b-reason-chat`` at a tiny size under
``JAX_PLATFORMS=cpu`` (both kernels in interpret mode), traced and
untraced; its lines carry ``"rehearsal": true`` and no number of them is a
device's. ``--broken`` takes the admission reset out of the engine (a slot
keeps the recurrent state its last sequence left) and requires `correct`
to come out false. ``--control`` puts the reference at float8 in the
program's place at the published widths and depth (2 rows of 96 tokens;
some minutes and 13 GB of host memory on the CPU) and requires the serving
limit to fail.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import selftest  # noqa: E402
from lib import check, harness  # noqa: E402

CELL = "serve-jamba2-3b-reason-chat"
TINY_MODEL = {"vocab_size": 256, "n_embd": 64, "n_head": 4, "kv_heads": 1,
              "n_layer": 4, "block_size": 64, "ffn_hidden": 96,
              "mamba_dt_rank": 8, "attn_layer_period": 2,
              "attn_layer_offset": 1, "compute_dtype": "float32",
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


def broken() -> None:
    """A slot that is not zeroed on admission hands its last sequence's
    recurrent state to the next one: the served tokens then lie far below
    the reference's best, whatever the limit."""
    def no_reset(engine):
        engine._reset_slot_state = lambda slot, iteration: None

    out = selftest.drive(tiny_cell(), 9, 2.0, 0, break_engine=no_reset)
    assert out["correct"] is False, "a slot pool that is never reset passed"


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
    assert not check.judge(rows, "jamba2-3b float8 control, serve"), \
        "the float8 control passed the serving limit"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--broken", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    rehearse()
    print("ok rehearse")
    for flag, fn in (("broken", broken), ("control", control)):
        if getattr(args, flag):
            fn()
            print(f"ok {flag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
