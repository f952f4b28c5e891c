"""Kernel-level sweep for the fused flash attention op: times forward and
forward+backward of flash_diff_attention at several sequence lengths and
tile configurations on the TPU (timed regions close with
``jax.block_until_ready``).

    python tools/flash_sweep.py [--steps 10] [--tiles 512,512,512,512 ...]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp


def bench_case(T, B, H, d, tiles, steps, mode):
    from differential_transformer_replication_tpu.ops.flash import (
        flash_diff_attention,
    )

    kw = {}
    if tiles is not None:
        kw = dict(
            block_q=tiles[0], block_k=tiles[1],
            block_q_train=tiles[2], block_k_train=tiles[3],
        )
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q1, k1, q2, k2 = (
        jax.random.normal(k, (B, T, H, d), jnp.bfloat16) for k in ks[:4]
    )
    v = jax.random.normal(ks[4], (B, T, H, 2 * d), jnp.bfloat16)
    lam = jax.random.uniform(ks[5], (H,), jnp.float32, 0.1, 0.7)

    if mode == "fwd":
        fn = jax.jit(
            lambda *a: jnp.sum(
                flash_diff_attention(*a, **kw).astype(jnp.float32)
            )
        )
    else:
        fn = jax.jit(
            jax.grad(
                lambda *a: jnp.sum(
                    flash_diff_attention(*a, **kw).astype(jnp.float32)
                )
            )
        )

    args = (q1, k1, q2, k2, v, lam)
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / steps
    return dt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument(
        "--tiles", nargs="*", default=None,
        help="tile configs as q,k,qt,kt (default: library default only)",
    )
    p.add_argument("--seqs", default="512,2048,8192")
    p.add_argument("--modes", default="fwd,grad")
    args = p.parse_args()
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("flash_sweep")
    configs = [None]
    if args.tiles:
        configs += [tuple(int(x) for x in t.split(",")) for t in args.tiles]

    for T in (int(s) for s in args.seqs.split(",")):
        # keep tokens-per-case roughly constant
        B = max(32 * 512 // T, 1)
        H, d = 4, 96
        for mode in args.modes.split(","):
            for tiles in configs:
                try:
                    dt = bench_case(T, B, H, d, tiles, args.steps, mode)
                    toks = B * T / dt
                    print(
                        f"T={T:6d} B={B:3d} {mode:4s} tiles={tiles or 'default'}: "
                        f"{dt * 1e3:8.2f} ms  {toks / 1e3:9.1f}k tok/s",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    print(
                        f"T={T:6d} B={B:3d} {mode:4s} tiles={tiles}: FAILED "
                        f"{type(e).__name__}: {str(e)[:120]}",
                        flush=True,
                    )


if __name__ == "__main__":
    main()
