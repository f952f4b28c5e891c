"""Kernel-level sweep for the fused FFN/norm path — flash_sweep.py's
sibling for ops/fused_ffn.py + ops/fused_norm_residual.py.

Four sweep axes, each printed as one JSON line per case:

  - impl: the fused Pallas chain vs the reference XLA composition
    (layer_norm + swiglu), fwd and fwd+grad, at several (rows, width)
    shapes — the kernel-level win the ffn_impl switch buys,
  - tiles: (block_m, block_f) candidates for the fused SwiGLU forward,
  - kernels (--kernels): the three SwiGLU kernels alone — the primal
    forward, the gradient's forward (which also writes the two
    pre-activations) and the backward that reads them — per forward
    tile (--tiles) and backward tile (--bwd-tiles); what
    ops/fused_ffn.py:_bwd_tiles was chosen from,
  - remat policies: full train-step timings per ModelConfig.remat_policy
    (--remat-policies): under remat the FFN's two saved pre-activations
    live for one block and the gradient's forward runs twice.

Timed regions close with ``jax.block_until_ready``, like flash_sweep.py.

    python tools/ffn_sweep.py [--steps 10] [--tiles 256,512 ...]
    python tools/ffn_sweep.py --kernels --rows 32768 \
        --bwd-tiles 256,512 256,1024 512,1024
    python tools/ffn_sweep.py --remat-policies none,dots --steps 5
    python tools/ffn_sweep.py --smoke     # tier-1 CI gate: tiny shapes,
                                          # interpret-mode kernels, ~seconds
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp


def bench_ffn_case(M, E, impl, tiles, steps, mode, dtype):
    """One (rows, width) case: the block's norm+SwiGLU chain, fused
    (pallas) or reference (xla). Returns seconds/step."""
    from differential_transformer_replication_tpu.ops import (
        layer_norm,
        swiglu,
    )
    from differential_transformer_replication_tpu.ops.fused_ffn import (
        fused_swiglu,
    )
    from differential_transformer_replication_tpu.ops.fused_norm_residual import (
        fused_add_norm,
    )

    F = 4 * E
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (M, E), dtype)
    d = jax.random.normal(ks[1], (M, E), dtype)
    lnw = jnp.ones((E,), jnp.float32)
    lnb = jnp.zeros((E,), jnp.float32)
    wg = jax.random.normal(ks[2], (E, F), jnp.float32) * 0.02
    bg = jnp.zeros((F,), jnp.float32)
    wx = jax.random.normal(ks[3], (E, F), jnp.float32) * 0.02
    bx = jnp.zeros((F,), jnp.float32)

    kw = {}
    if tiles is not None:
        kw = dict(block_m=tiles[0], block_f=tiles[1])

    def fused(x, d, lnw, lnb, wg, bg, wx, bx):
        xn, n = fused_add_norm(x, d, lnw, lnb)
        h = fused_swiglu(n, wg, bg, wx, bx, **kw)
        return jnp.sum(h.astype(jnp.float32)) + jnp.sum(
            xn.astype(jnp.float32)
        )

    def reference(x, d, lnw, lnb, wg, bg, wx, bx):
        xn = x + d
        n = layer_norm(xn, lnw, lnb)
        h = swiglu(
            n, wg.astype(x.dtype), bg.astype(x.dtype),
            wx.astype(x.dtype), bx.astype(x.dtype),
        )
        return jnp.sum(h.astype(jnp.float32)) + jnp.sum(
            xn.astype(jnp.float32)
        )

    base = fused if impl == "pallas" else reference
    fn = jax.jit(base if mode == "fwd" else jax.grad(base, argnums=(0, 4, 6)))
    args = (x, d, lnw, lnb, wg, bg, wx, bx)
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def bench_kernel_case(M, E, kernel, tiles, steps, dtype):
    """One SwiGLU kernel alone at (rows, width): ``fwd`` (the primal),
    ``fwd_res`` (the gradient's forward) under forward ``tiles``, or
    ``bwd`` under backward ``tiles`` (None: the library's own choice).
    The backward's g/t are donated and its dg/dt fed back, as the
    custom VJP's aliasing has it. Returns seconds/call."""
    from differential_transformer_replication_tpu.ops import fused_ffn

    F = 4 * E
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (M, E), dtype)
    interpret = fused_ffn.auto_interpret()
    if kernel == "bwd":
        bwd = jax.jit(
            lambda x, g, t, gh: fused_ffn._bwd_call(
                x, g, t, gh, interpret=interpret
            ),
            donate_argnums=(1, 2),
        )
        g, t, gh = (jax.random.normal(k, (M, F), dtype) for k in ks[1:])

        def call(out):
            return bwd(x, out[0], out[1], gh)

        # the tiles are read when the first call traces the kernel
        choice = fused_ffn._bwd_tiles if tiles is None else lambda *_: tiles
        with mock.patch.object(fused_ffn, "_bwd_tiles", choice):
            out = call((g, t))
    else:
        wg, wx = (jax.random.normal(k, (E, F), dtype) * 0.02 for k in ks[1:3])
        b = jnp.zeros((1, F), dtype)
        bm, bf = tiles or (
            fused_ffn._DEFAULT_BLOCK_M, fused_ffn._DEFAULT_BLOCK_F
        )
        fwd = jax.jit(lambda x, wg, wx: fused_ffn._fwd_call(
            x, wg, b, wx, b, block_m=bm, block_f=bf, interpret=interpret,
            residuals=kernel == "fwd_res",
        ))

        def call(_):
            return fwd(x, wg, wx)

        out = call(None)
    jax.block_until_ready(out)  # compiled + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        out = call(out)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def bench_remat_case(policy, ffn_impl, steps, args):
    """Full train-step seconds/step under remat with one save policy:
    ``off`` keeps every block's residuals (the FFN's two saved
    pre-activations among them), a policy replays the block's forward."""
    from differential_transformer_replication_tpu.config import (
        ModelConfig,
        TrainConfig,
    )
    from differential_transformer_replication_tpu.train.step import (
        create_train_state,
        make_train_step,
    )

    model = ModelConfig(
        model=args.model, vocab_size=args.vocab_size, n_embd=args.n_embd,
        n_head=args.n_head, n_layer=args.n_layer, block_size=args.block_size,
        dropout=0.0, compute_dtype=args.dtype, attention_impl=args.attn,
        ffn_impl=ffn_impl, remat=policy != "off", remat_policy=(
            "none" if policy == "off" else policy
        ),
    )
    cfg = TrainConfig(
        model=model, micro_batch_size=args.micro_batch, grad_acc_steps=1
    )
    state = create_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg)
    x = jax.random.randint(
        jax.random.PRNGKey(1), (1, args.micro_batch, model.block_size), 0,
        model.vocab_size,
    )
    batch = {"x": x, "y": jnp.roll(x, -1, -1)}
    state, m = step(state, batch)  # compile
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    return (time.perf_counter() - t0) / steps


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument(
        "--tiles", nargs="*", default=None,
        help="fused-kernel tile configs as block_m,block_f "
             "(default: library default only)",
    )
    p.add_argument(
        "--kernels", action="store_true",
        help="time the three SwiGLU kernels alone instead of the chain",
    )
    p.add_argument(
        "--bwd-tiles", nargs="*", default=None,
        help="backward-kernel tile configs as block_m,block_f for "
             "--kernels (default: the library's choice only)",
    )
    p.add_argument("--rows", default="4096,16384",
                   help="M = B*T row counts for the kernel-level sweep")
    p.add_argument("--width", type=int, default=768, help="E (hidden = 4E)")
    p.add_argument("--modes", default="fwd,grad")
    p.add_argument("--impls", default="xla,pallas")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument(
        "--remat-policies", default=None,
        help="comma list from off,none,dots,dots_no_batch,nothing,"
             "everything: time a FULL train step per policy instead of "
             "the bare chain",
    )
    # full-step knobs (remat mode)
    p.add_argument("--model", default="diff",
                   choices=["control", "diff", "ndiff"])
    p.add_argument("--attn", default="pallas", choices=["xla", "pallas"])
    p.add_argument("--ffn", default="pallas", choices=["xla", "pallas"])
    p.add_argument("--micro-batch", type=int, default=32)
    p.add_argument("--block-size", type=int, default=512)
    p.add_argument("--n-embd", type=int, default=768)
    p.add_argument("--n-head", type=int, default=4)
    p.add_argument("--n-layer", type=int, default=8)
    p.add_argument("--vocab-size", type=int, default=12000)
    p.add_argument(
        "--smoke", action="store_true",
        help="CI gate: tiny shapes + 2 steps so the interpret-mode "
             "kernels run end to end in seconds; exit nonzero on any "
             "case failure",
    )
    args = p.parse_args()
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("ffn_sweep", smoke=args.smoke)
    if args.smoke:
        args.rows, args.width, args.steps = "64", 32, 2
        args.n_embd, args.n_head, args.n_layer = 32, 2, 2
        args.vocab_size, args.block_size, args.micro_batch = 64, 16, 2
        if args.remat_policies is None:
            args.remat_policies = "off,none,dots"
        args.kernels, args.bwd_tiles = True, ["8,64"]

    dtype = jnp.dtype(args.dtype)
    failed = 0

    def parse(tiles):
        return [None] + [
            tuple(int(v) for v in t.split(",")) for t in tiles or []
        ]

    configs = parse(args.tiles)
    kernel_cases = []
    if args.kernels:
        kernel_cases = [
            (k, t) for k in ("fwd", "fwd_res") for t in configs
        ] + [("bwd", t) for t in parse(args.bwd_tiles)]
    # --kernels replaces the chain sweep (the smoke gate runs both)
    chain_modes = args.modes.split(",")
    if args.kernels and not args.smoke:
        chain_modes = []
    for M in (int(s) for s in args.rows.split(",")):
        for kernel, tiles in kernel_cases:
            try:
                dt = bench_kernel_case(
                    M, args.width, kernel, tiles, args.steps, dtype
                )
                print(json.dumps({
                    "case": "ffn_kernel", "rows": M, "width": args.width,
                    "kernel": kernel, "tiles": tiles,
                    "ms": round(dt * 1e3, 3),
                }), flush=True)
            except Exception as e:  # noqa: BLE001
                failed += 1
                print(json.dumps({
                    "case": "ffn_kernel", "rows": M, "kernel": kernel,
                    "tiles": tiles, "failed":
                    f"{type(e).__name__}: {str(e)[:160]}",
                }), flush=True)
        for mode in chain_modes:
            for impl in args.impls.split(","):
                for tiles in configs if impl == "pallas" else [None]:
                    try:
                        dt = bench_ffn_case(
                            M, args.width, impl, tiles, args.steps, mode,
                            dtype,
                        )
                        print(json.dumps({
                            "case": "ffn_chain", "rows": M,
                            "width": args.width, "mode": mode,
                            "impl": impl, "tiles": tiles,
                            "ms": round(dt * 1e3, 3),
                            "rows_per_s": round(M / dt, 1),
                        }), flush=True)
                    except Exception as e:  # noqa: BLE001
                        failed += 1
                        print(json.dumps({
                            "case": "ffn_chain", "rows": M, "mode": mode,
                            "impl": impl, "tiles": tiles, "failed":
                            f"{type(e).__name__}: {str(e)[:160]}",
                        }), flush=True)

    if args.remat_policies:
        for policy in args.remat_policies.split(","):
            try:
                dt = bench_remat_case(policy, args.ffn, args.steps, args)
                toks = args.micro_batch * args.block_size / dt
                print(json.dumps({
                    "case": "remat_step", "policy": policy,
                    "ffn_impl": args.ffn, "model": args.model,
                    "ms_per_step": round(dt * 1e3, 2),
                    "tokens_per_s": round(toks, 1),
                }), flush=True)
            except Exception as e:  # noqa: BLE001
                failed += 1
                print(json.dumps({
                    "case": "remat_step", "policy": policy, "failed":
                    f"{type(e).__name__}: {str(e)[:160]}",
                }), flush=True)

    if failed:
        print(f"[ffn_sweep] {failed} case(s) FAILED", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
