"""The fused SwiGLU kernels' share of their roofline in a traced train
step: the least time the chip could take for what their job NEEDS a step
(:func:`ffn_need`, turned into seconds by ``lib/cost.py:least_seconds``)
over the device time of the ``fused_ffn_fwd`` and ``fused_ffn_bwd``
kernels a step (``ffn_kernel_ms_per_step``'s number)."""

from lib import cost, harness, xplane


def ffn_need(model: dict, rows: int, seq: int) -> dict:
    """``silu(x Wg + bg) * (x Wx + bx)`` and its backward as far as the
    kernels own it (ops/fused_ffn.py), for ``rows`` sequences of ``seq``
    tokens through every layer; M tokens, E wide, hidden F = 4 E.

    Operations, 2 a multiply-add: forward the gate and the xform matmul
    (2 M E F each); backward dWg and dWx (2 M E F each). The backward's
    recomputation of both pre-activations is the kernel's choice and does
    not count; ``dg Wg^T + dt Wx^T`` (dx) is left to XLA and is not the
    kernels' job; the elementwise SiLU, product and bias sums are under 1%.

    Bytes, each array once: forward reads x (bf16) and both weights and
    writes h; backward reads x, both weights and dh and writes dg, dt
    (bf16) and dWg, dWx (float32). Biases are left out."""
    E = model["n_embd"]
    F, M = 4 * E, rows * seq
    flops = 4 * 2.0 * M * E * F
    fwd = 2 * (M * E + 2 * E * F + M * F)
    bwd = 2 * (M * E + 2 * E * F + M * F) + 2 * (2 * M * F) + 4 * (2 * E * F)
    L = model["n_layer"]
    return {"flops": L * flops, "bytes": float(L * (fwd + bwd))}


def read(run):
    v = run.values
    if (run.planes is None or run.env.peaks is None
            or not v.get("trace_steps")):
        return None
    needles = harness.load_json(
        "layer_metrics", "fused_ffn_roofline.json")["source"]["needles"]
    total, count = xplane.needle_seconds(run.planes, needles)
    if not count:
        return None
    secs = total / v["trace_steps"]
    need = ffn_need(run.cell.config["model"], v["rows_per_chip"],
                    v["seq_len"])
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline fused_ffn: {v['rows_per_chip']} rows of "
                f"{v['seq_len']} a chip-step; {need['flops']:.4g} "
                f"operations, {need['bytes']:.4g} bytes "
                f"({need['flops'] / need['bytes']:.1f} operations a byte); "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured ({count / v['trace_steps']:.0f} "
                "kernels a step)")
    return 100.0 * least / secs
