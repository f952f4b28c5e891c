"""The chunk scan's share of its roofline in the traced part of a serve
window: the least time the chip could take for what the scans of the
traced prefill calls NEED (:func:`scan_need`, turned into seconds by
``lib/cost.py:least_seconds``) over the device time of the
``ssm_scan_fwd`` kernels in the trace."""

from lib import cost, harness, jamba_sizes, xplane


def scan_need(model: dict, tokens: int, calls: int) -> dict:
    """``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t; y_t = h_t . C_t
    + D u_t`` over ``tokens`` tokens in ``calls`` chunks, every Mamba
    layer; Di channels of N states.

    Operations a token, channel and state: the product ``delta A`` and its
    exponential (2), the decay and the input's product and sum (3), the
    read-out's product and sum (2); a token and channel: ``delta u`` and
    the skip (3). Bytes, each array once: a token reads u (bf16), delta
    (float32) and B, C (float32, N each) and writes y (float32); a call
    reads A and D and reads and writes the state (float32). The chunk's
    padding to whole time blocks and the copies of B and C spread over a
    lane tile are the kernel's choice and do not count."""
    s = jamba_sizes.sizes(model)
    Di, N, layers = s["Di"], s["N"], s["mamba"]
    flops = tokens * Di * (7.0 * N + 3.0)
    per_token = Di * (2 + 4 + 4) + 2 * N * 4
    per_call = (Di * N + Di + 2 * Di * N) * 4
    return {"flops": layers * flops,
            "bytes": float(layers * (tokens * per_token + calls * per_call))}


def traced_prefill_calls(run) -> list:
    """The chunk lengths of the ``prefill_call`` spans inside the traced
    part of the window (its last ``trace_seconds``, at most half of it:
    lib/open_loop_cell.py)."""
    t0, t1 = run.values["measured_window"]
    p0 = t1 - min(run.cell.traffic["trace_seconds"], (t1 - t0) / 2)
    return [args["size"] for n, a, b, args in list(run.spans.spans)
            if n == "prefill_call" and p0 <= a and b <= t1 and args]


def read(run):
    if run.planes is None or run.env.peaks is None or run.spans is None:
        return None
    needles = harness.load_json(
        "layer_metrics", "ssm_scan_roofline.json")["source"]["needles"]
    secs, count = xplane.needle_seconds(run.planes, needles)
    sizes = traced_prefill_calls(run)
    if not count or not sizes:
        return None
    need = scan_need(run.cell.config["model"], sum(sizes), len(sizes))
    least, bound = cost.least_seconds(need, run.env.peaks)
    harness.say(f"roofline ssm_scan: {len(sizes)} prefill calls of "
                f"{sum(sizes)} tokens traced, {count:.0f} kernels; "
                f"{need['flops']:.4g} operations, {need['bytes']:.4g} bytes; "
                f"{bound}-bound, least {least * 1e3:.4f} ms against "
                f"{secs * 1e3:.4f} ms measured")
    return 100.0 * least / secs
