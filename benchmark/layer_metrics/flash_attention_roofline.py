"""Flash attention's share of its roofline in a traced train step: the
least time the chip could take for what attention NEEDS a step
(:func:`attention_need`, turned into seconds by ``lib/cost.py:
least_seconds``) over the device time of the ``flash_attention`` bucket a
step (``attn_kernel_ms_per_step``'s number)."""

from lib import cost, harness, xplane


def attention_need(model: dict, rows: int, seq: int) -> dict:
    """Causal attention, forward and backward, for ``rows`` sequences of
    ``seq`` tokens through every layer, at the real head widths (the
    recipe's 96 and 192, not the lanes a kernel pads them to).

    Operations, 2 a multiply-add, over the causal half of the score
    matrix (seq * (seq + 1) / 2 query-key pairs a head): forward the
    scores of every stream (width d) and one product of the combined
    probabilities with V (width dv); backward dV and dP once a head
    (dv), dQ and dK of every stream (d). The backward's recomputation of
    the scores is the kernel's choice and does not count.

    Bytes, bf16, each array once: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV. Q and K hold
    every stream; log-sum-exp rows and lambda vectors are left out
    (under 1%)."""
    S, H, d, dv = cost._attn_sizes(model)
    pairs = seq * (seq + 1) / 2
    flops_head = 2.0 * pairs * (3 * S * d + 3 * dv)
    qk = S * H * seq * d * 2  # bytes of Q, or of K, of one sequence
    vo = H * seq * dv * 2  # bytes of V, or of O
    bytes_seq = (2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo)
    per = model["n_layer"] * rows
    return {"flops": per * H * flops_head, "bytes": float(per * bytes_seq)}


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or not v.get("trace_steps")):
        return None
    total, count = xplane.bucket_seconds(run.planes, "flash_attention")
    if not count:
        return None
    secs = total / v["trace_steps"]
    need = attention_need(run.cell.config["model"], v["rows_per_chip"],
                          v["seq_len"])
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline flash_attention: {v['rows_per_chip']} rows of "
                f"{v['seq_len']} a chip-step; {need['flops']:.4g} "
                f"operations, {need['bytes']:.4g} bytes "
                f"({need['flops'] / need['bytes']:.1f} operations a byte); "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured ({count / v['trace_steps']:.0f} "
                "kernels a step)")
    return 100.0 * least / secs
