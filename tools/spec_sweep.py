"""Speculative-decoding sweep — ffn_sweep.py's sibling for
serving/spec.py + the fused multi-row verify step.

One JSON line per case, sweeping:

  - drafter kind: the n-gram/prompt-lookup fallback, a model drafter
    sharing the target's params ("self" — the acceptance~1 upper bound
    of the verify machinery), or a smaller random-init control drafter
    beside each target family (the realistic pairing; random weights
    mean near-zero acceptance, which is exactly the overhead floor
    worth charting),
  - draft length k (the compiled verify-ladder rung),
  - verify formulation: "exact" (bit-identical unroll) vs "batched"
    (the fused multi-query kernel pass),
  - target family (control / diff / ndiff).

Each case runs the SAME greedy workload non-spec and spec-enabled on
fresh engines (jitted closures are module-cached, so the measured pass
is warm) and reports acceptance rate, tok/s for both arms, the
speedup, and greedy token agreement.

    python tools/spec_sweep.py [--draft-lens 2,4,8] [--requests 16]
    python tools/spec_sweep.py --smoke    # tier-1 CI gate: parity-
                                          # asserted tiny cases, seconds
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np

from serve_bench import spec_workload  # noqa: E402  (shared driver)


def run_case(model_cfg, params, drafter, mode, verify, k, prompts,
             new_tokens, clients, seed):
    """One sweep case: baseline + spec arms, warm pass + measured pass
    each. Returns the JSON-ready result dict."""
    from differential_transformer_replication_tpu.config import (
        ServingConfig,
    )
    from differential_transformer_replication_tpu.serving import (
        ServingClient,
        ServingEngine,
    )

    def _arm(spec_on):
        serving = ServingConfig(
            num_slots=min(8, len(prompts)), prefill_chunk=8,
            prefill_budget=32,
            spec_mode=mode if spec_on else "",
            spec_draft_len=k, spec_verify=verify,
            max_seq_len=model_cfg.block_size + new_tokens,
        )
        stats = None
        for _ in range(2):  # warm pass, then measured pass
            engine = ServingEngine(
                params, model_cfg, serving,
                spec_drafter=drafter if spec_on else None,
            )
            client = ServingClient(engine)
            wall, toks, outs = spec_workload(
                client, prompts, new_tokens, clients, seed, 0.0
            )
            stats = engine.spec_stats() if spec_on else None
            client.close()
        return wall, toks, outs, stats

    b_wall, b_toks, b_out, _ = _arm(False)
    s_wall, s_toks, s_out, stats = _arm(True)
    total = sum(len(t) for t in b_out.values())
    agree = sum(
        1 for i, t in b_out.items()
        for a, b in zip(t, s_out.get(i, [])) if a == b
    )
    b_tps = b_toks / b_wall
    s_tps = s_toks / s_wall
    return {
        "metric": "spec_sweep_case",
        "model": model_cfg.model,
        "drafter": mode if mode == "ngram" else "model",
        "spec_verify": verify,
        "draft_len": k,
        "acceptance_rate": stats["acceptance_rate"],
        "proposed": stats["proposed"],
        "accepted": stats["accepted"],
        "baseline_tok_per_s": round(b_tps, 1),
        "spec_tok_per_s": round(s_tps, 1),
        "speedup": round(s_tps / b_tps, 3) if b_tps else None,
        "greedy_token_match_rate": round(agree / max(1, total), 5),
        "n_requests": len(prompts),
        "new_tokens": new_tokens,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--models", default="control,diff,ndiff")
    p.add_argument("--draft-lens", default="2,4,8")
    p.add_argument("--verify", default="exact,batched")
    p.add_argument("--drafters", default="ngram,self,control",
                   help="comma list: ngram | self (model drafter = "
                        "target params) | control (small random-init "
                        "control drafter)")
    p.add_argument("--n-embd", type=int, default=64)
    p.add_argument("--n-layer", type=int, default=2)
    p.add_argument("--n-head", type=int, default=2)
    p.add_argument("--block-size", type=int, default=64)
    p.add_argument("--vocab-size", type=int, default=128)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--new-tokens", type=int, default=24)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None,
                   help="also append the JSON lines to this file")
    p.add_argument("--smoke", action="store_true",
                   help="tier-1 CI gate: one tiny case per drafter "
                        "kind, greedy parity ASSERTED for the exact "
                        "verify mode")
    args = p.parse_args()
    from differential_transformer_replication_tpu.utils.device import (
        start_measurement,
    )

    start_measurement("spec_sweep", smoke=args.smoke)
    if args.smoke:
        args.models = "control"
        args.draft_lens = "4"
        args.verify = "exact,batched"
        args.drafters = "ngram,self"
        args.n_embd, args.n_layer, args.block_size = 32, 2, 32
        args.vocab_size, args.requests, args.clients = 61, 6, 3
        args.new_tokens = 10

    import jax

    from differential_transformer_replication_tpu.config import (
        ModelConfig,
    )
    from differential_transformer_replication_tpu.models import (
        init_model,
    )

    rng = np.random.default_rng(args.seed)
    lines = []
    for kind in args.models.split(","):
        cfg = ModelConfig(
            model=kind, vocab_size=args.vocab_size, n_embd=args.n_embd,
            n_head=args.n_head, n_layer=args.n_layer,
            block_size=args.block_size, dropout=0.0, n_terms=3,
            compute_dtype="float32",
        )
        params = init_model(jax.random.PRNGKey(args.seed), cfg)
        max_prompt = max(2, args.block_size - args.new_tokens - 1)
        prompts = []
        for _ in range(args.requests):
            n = int(rng.integers(2, min(12, max_prompt) + 1))
            period = int(rng.integers(2, min(5, n + 1)))
            cyc = rng.integers(0, args.vocab_size, size=period).tolist()
            prompts.append((cyc * (n // period + 1))[:n])
        for dk in args.drafters.split(","):
            if dk == "ngram":
                mode, drafter = "ngram", None
            elif dk == "self":
                mode, drafter = "model", (params, cfg)
            else:  # a smaller random-init control drafter
                d_cfg = ModelConfig(
                    model="control", vocab_size=args.vocab_size,
                    n_embd=max(16, args.n_embd // 2), n_head=args.n_head,
                    n_layer=1, block_size=args.block_size, dropout=0.0,
                    compute_dtype="float32",
                )
                mode = "model"
                drafter = (
                    init_model(jax.random.PRNGKey(args.seed + 1), d_cfg),
                    d_cfg,
                )
            for verify in args.verify.split(","):
                for k in (int(x) for x in args.draft_lens.split(",")):
                    line = run_case(
                        cfg, params, drafter, mode, verify, k, prompts,
                        args.new_tokens, args.clients, args.seed,
                    )
                    line["drafter"] = dk
                    print(json.dumps(line))
                    lines.append(line)
                    if args.smoke and verify == "exact":
                        assert line["greedy_token_match_rate"] == 1.0, (
                            f"exact-verify greedy parity broke: {line}"
                        )
                    if args.smoke and dk == "self":
                        assert line["acceptance_rate"] == 1.0, (
                            f"self-drafter must accept everything: "
                            f"{line}"
                        )
    if args.out:
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
