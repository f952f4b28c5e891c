"""Decode-attention kernel sweep — ffn_sweep.py's sibling for
ops/decode_attention.py.

Times the fused Pallas single-query (slot-pool) attention kernel against
its plain-XLA twin across the axes that matter for serving capacity:

  - cache length M (the ring/block size — the HBM stream per slot),
  - slot count B (the batched pool width),
  - model family (control S=1, diff S=2, ndiff S=N combine streams),
  - KV dtype (bf16/float vs per-head-scale int8 with in-kernel dequant).

One JSON line per (impl, family, B, M, kv_dtype) case with ms/step and
the max |pallas - xla| parity delta for that case's inputs, e.g.::

    {"impl": "pallas", "model": "diff", "batch": 8, "cache_len": 512,
     "kv_dtype": "int8", "ms_per_step": ..., "max_abs_diff": ...}

Timed regions close with ``jax.block_until_ready``, like
flash_sweep.py/ffn_sweep.py.

    python tools/decode_attn_sweep.py --batches 8 32 --cache-lens 512 2048
    python tools/decode_attn_sweep.py --smoke   # tier-1 CI gate: tiny
                                                # shapes, interpret-mode
                                                # kernel, parity-asserted
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp


_FAMILY_STREAMS = {"control": 1, "diff": 2, "ndiff": 4}


def _case_inputs(model, B, M, H, d, kv_dtype, dtype, seed=0):
    """Random pool-shaped decode inputs: per-stream queries, a ring
    cache filled to staggered per-row depths (like a live slot pool),
    quantized when kv_dtype == "int8"."""
    from differential_transformer_replication_tpu.ops.decode_attention import (
        quantize_kv,
    )

    S = _FAMILY_STREAMS[model]
    dv = d if model == "control" else 2 * d
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qs = jax.random.normal(ks[0], (S, B, H, d), dtype)
    k = jax.random.normal(ks[1], (S, B, H, M, d), dtype)
    v = jax.random.normal(ks[2], (B, H, M, dv), dtype)
    # staggered fill depths across rows, full cache on row 0; clamp at
    # 0 (min one visible slot) — B > M/2 strides below the ring floor,
    # where the reference's all-masked softmax is NaN
    pos = jnp.maximum(
        M - 1 - (jnp.arange(B) * max(1, M // (2 * B))), 0
    ).astype(jnp.int32)
    coeffs = jax.random.uniform(
        ks[3], (S, H), jnp.float32, minval=-1.0, maxval=1.0
    )
    scales = None
    if kv_dtype == "int8":
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        k, v, scales = kq, vq, (ksc, vsc)
    return qs, k, v, pos, coeffs, scales


def bench_case(model, B, M, H, d, kv_dtype, steps, dtype):
    """One sweep case: returns [(impl, seconds/step)] plus the parity
    delta between the two impls on identical inputs."""
    from differential_transformer_replication_tpu.ops.decode_attention import (
        decode_attention,
        decode_attention_reference,
        dequantize_kv,
    )

    qs, k, v, pos, coeffs, scales = _case_inputs(
        model, B, M, H, d, kv_dtype, dtype
    )

    if scales is None:

        def fused(qs, k, v, pos, coeffs):
            return decode_attention(qs, k, v, pos, coeffs)

        def reference(qs, k, v, pos, coeffs):
            return decode_attention_reference(qs, k, v, pos, coeffs)

        args = (qs, k, v, pos, coeffs)
    else:
        ksc, vsc = scales

        def fused(qs, k, v, pos, coeffs, ksc, vsc):
            return decode_attention(
                qs, k, v, pos, coeffs, k_scale=ksc, v_scale=vsc
            )

        def reference(qs, k, v, pos, coeffs, ksc, vsc):
            return decode_attention_reference(
                qs, dequantize_kv(k, ksc, qs.dtype),
                dequantize_kv(v, vsc, qs.dtype), pos, coeffs,
            )

        args = (qs, k, v, pos, coeffs, ksc, vsc)

    out = {}
    results = {}
    for impl, fn in (("pallas", fused), ("xla", reference)):
        jf = jax.jit(fn)
        results[impl] = jf(*args)
        jax.block_until_ready(results[impl])  # compile + warm
        t0 = time.perf_counter()
        r = None
        for _ in range(steps):
            r = jf(*args)
        jax.block_until_ready(r)
        out[impl] = (time.perf_counter() - t0) / steps
    diff = float(
        jnp.max(
            jnp.abs(
                results["pallas"].astype(jnp.float32)
                - results["xla"].astype(jnp.float32)
            )
        )
    )
    return out, diff


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--models", nargs="+",
                   default=["control", "diff", "ndiff"],
                   choices=["control", "diff", "ndiff"])
    p.add_argument("--batches", type=int, nargs="+", default=[8, 32],
                   help="slot-pool widths")
    p.add_argument("--cache-lens", type=int, nargs="+",
                   default=[512, 2048], help="ring cache lengths M")
    p.add_argument("--kv-dtypes", nargs="+", default=["bf16", "int8"],
                   choices=["bf16", "int8"])
    p.add_argument("--n-head", type=int, default=4)
    p.add_argument("--head-size", type=int, default=96,
                   help="per-head q/k width (the diff recipe's 96)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--smoke", action="store_true",
                   help="tiny interpret-mode shapes + parity assertions; "
                        "seconds on CPU (the tier-1 gate)")
    p.add_argument("--out", default=None,
                   help="also append the JSON lines to this file")
    args = p.parse_args()
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("decode_attn_sweep", smoke=args.smoke)
    if args.smoke:
        args.batches, args.cache_lens = [4], [32]
        args.n_head, args.head_size = 2, 16
        args.steps, args.dtype = 2, "float32"

    rows = []
    for model in args.models:
        for B in args.batches:
            for M in args.cache_lens:
                for kvd in args.kv_dtypes:
                    secs, diff = bench_case(
                        model, B, M, args.n_head, args.head_size, kvd,
                        args.steps, jnp.dtype(args.dtype),
                    )
                    for impl, s in secs.items():
                        row = {
                            "impl": impl, "model": model, "batch": B,
                            "cache_len": M, "kv_dtype": kvd,
                            "n_head": args.n_head,
                            "head_size": args.head_size,
                            "dtype": args.dtype,
                            "ms_per_step": round(s * 1e3, 4),
                            "max_abs_diff": diff,
                        }
                        rows.append(row)
                        print(json.dumps(row))
                    if args.smoke:
                        # both impls consumed IDENTICAL (already
                        # quantized) inputs, so the only divergence is
                        # the online-vs-materialized softmax accumulation
                        # order — tile-level fp32 noise, not quant error
                        assert diff < 1e-5, (
                            f"{model}/{kvd}: pallas vs xla diverged "
                            f"by {diff}"
                        )
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    by = {}
    for r in rows:
        key = (r["model"], r["batch"], r["cache_len"], r["kv_dtype"])
        by.setdefault(key, {})[r["impl"]] = r["ms_per_step"]
    for key, d in sorted(by.items()):
        if "xla" in d and "pallas" in d and d["pallas"] > 0:
            print(
                f"# {key[0]} B={key[1]} M={key[2]} {key[3]}: "
                f"fused speedup {d['xla'] / d['pallas']:.2f}x",
                file=sys.stderr,
            )


if __name__ == "__main__":
    main()
