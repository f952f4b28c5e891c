"""Decode throughput: cached vs windowed generation, batched and batch-1.

VERDICT r3 item 6: the batch-1 ring-cache number (1.6x at recipe width)
understates the cache because batch-1 per-token cost is FFN-dominated; at
B in {8, 32} attention is the dominant per-token term and the O(T^2) ->
O(T) win shows at its real operating point. This tool times, at the
recipe width (8L/768d control — the RoPE family that can decode past
block_size):

  - ``models.generate``      — the reference's windowed recompute
                               (control.py:163-171: full forward per token),
  - ``models.decode.generate_cached`` — the ring KV cache (O(T)/token).

One JSON line per (impl, batch) with tokens/sec (= B * new_tokens /
wall), each call closed by ``jax.block_until_ready``.

    python tools/decode_bench.py --batches 1 8 32 --new-tokens 1024
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batches", type=int, nargs="+", default=[1, 8, 32])
    p.add_argument("--new-tokens", type=int, default=1024)
    p.add_argument("--prompt-len", type=int, default=256)
    p.add_argument("--model", default="control",
                   choices=["control", "diff", "ndiff"])
    p.add_argument("--n-embd", type=int, default=768)
    p.add_argument("--n-layer", type=int, default=8)
    p.add_argument("--n-head", type=int, default=8,
                   help="control at the reference's head-doubled width")
    p.add_argument("--block-size", type=int, default=512)
    p.add_argument("--decode-attention-impl", default="xla",
                   choices=("xla", "pallas"),
                   help="decode attention backend for the cached path: "
                        "the fused Pallas single-query kernel "
                        "(ops/decode_attention.py) or the plain XLA "
                        "composition")
    p.add_argument("--kv-cache-dtype", default="auto",
                   choices=("auto", "bf16", "int8"),
                   help="KV-cache storage dtype; int8 = per-head-scale "
                        "quantized K/V (half the bf16 bytes)")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax

    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("decode_bench")
    from differential_transformer_replication_tpu.config import ModelConfig
    from differential_transformer_replication_tpu.models import (
        generate,
        init_model,
    )
    from differential_transformer_replication_tpu.models.decode import (
        generate_cached,
    )

    cfg = ModelConfig(
        model=args.model, vocab_size=12000, n_embd=args.n_embd,
        n_head=args.n_head, n_layer=args.n_layer,
        block_size=args.block_size, dropout=0.0,
        compute_dtype="bfloat16",
        decode_attention_impl=args.decode_attention_impl,
        kv_cache_dtype=args.kv_cache_dtype,
    )
    params = init_model(jax.random.PRNGKey(0), cfg)
    rows = []
    for B in args.batches:
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (B, args.prompt_len), 0, cfg.vocab_size
        )
        for name, fn in (
            ("windowed", lambda: generate(
                params, prompt, cfg, args.new_tokens, jax.random.PRNGKey(2)
            )),
            ("cached", lambda: generate_cached(
                params, prompt, cfg, args.new_tokens, jax.random.PRNGKey(2)
            )),
        ):
            jax.block_until_ready(fn())  # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
            tps = B * args.new_tokens / dt
            row = {
                "impl": name, "batch": B, "new_tokens": args.new_tokens,
                "prompt_len": args.prompt_len, "model": args.model,
                "decode_attention_impl": args.decode_attention_impl,
                "kv_cache_dtype": args.kv_cache_dtype,
                "tokens_per_sec": round(tps, 1), "wall_s": round(dt, 2),
            }
            rows.append(row)
            print(json.dumps(row))
    by = {}
    for r in rows:
        by.setdefault(r["batch"], {})[r["impl"]] = r["tokens_per_sec"]
    for b, d in sorted(by.items()):
        if "windowed" in d and "cached" in d:
            print(
                f"# B={b}: cache speedup {d['cached'] / d['windowed']:.2f}x",
                file=sys.stderr,
            )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
