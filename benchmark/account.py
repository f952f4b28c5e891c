"""The account of an iteration, from one traced run of a serve cell:

    python3 benchmark/account.py --workload <name> --seed <n> --seconds 10

``run.py --trace 1`` with two more lines above the result (PERF.md
section 5 is written from them):

- ``account``: over the measured window's PROMPT-FREE iterations (no
  ``prefill`` span: what the median iteration of every serve cell is),
  the host's mean ms an iteration inside each span, a parent's time given
  as what its sub-spans leave (``decode`` beside ``decode_h2d`` and
  ``decode_dispatch``), so the names add up to the iteration from its
  ``schedule`` start to its ``emit`` end but for ``unspanned``; beside
  them ``between`` (``step_tail``, ``deliver``, ``intake`` over all the
  window's iterations, as ``between_iterations_ms`` counts), how much
  of ``decode`` and of ``sample`` their sub-spans cover over the whole
  window, and the traced run's own mean token gap (an untraced run's
  ``itl_mean_ms`` beside it is what tracing costs when on);
- ``idle_by_span``: the device's idle seconds of the traced stretch under
  every host span's path, ALL names (``breakdown.idle_gaps`` keeps ten),
  which add up to the idle share x the traced window.

It times nothing itself: the spans are the engine's, the reductions
``lib/host_share.py``'s.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402
from lib import engine_spans, harness, host_share  # noqa: E402
from lib.stats import percentile  # noqa: E402

SUB_SPANS = {"decode": ("decode_h2d", "decode_dispatch"),
             "sample": ("sample_operands", "sample_dispatch", "token_read",
                        "load_read")}
BETWEEN = ("step_tail", "deliver", "intake")


def account(run) -> dict:
    t0, t1 = run.values["measured_window"]
    spans = [s for s in list(run.spans.spans) if t0 <= s[2] < t1]
    by_iter: dict = {}
    for name, a, b, args in spans:
        it = (args or {}).get("iteration")
        if it is not None:
            by_iter.setdefault(it, []).append((name, a, b))
    free = {it: ss for it, ss in by_iter.items()
            if not any(n == "prefill" for n, _, _ in ss)
            and any(n == "emit" for n, _, _ in ss)}
    ms: dict = {}
    lengths = []
    for ss in free.values():
        lengths.append((max(b for _, _, b in ss) - min(a for _, a, _ in ss))
                       * 1e3)
        mine: dict = {}
        for name, a, b in ss:
            mine[name] = mine.get(name, 0.0) + (b - a) * 1e3
        for parent, parts in SUB_SPANS.items():
            if parent in mine:  # a parent keeps what its parts leave
                mine[parent] -= sum(mine.get(c, 0.0) for c in parts)
        for name, v in mine.items():
            ms[name] = ms.get(name, 0.0) + v
    n = max(1, len(free))
    out = {name: v / n for name, v in sorted(ms.items())}
    out["unspanned"] = sum(lengths) / n - sum(out.values())
    total: dict = {}  # a completed prompt's sampler call is not `sample`'s
    for name, a, b, args in spans:
        if (args or {}).get("path", "decode") == "decode":
            total[name] = total.get(name, 0.0) + (b - a)
    gaps = run.values.get("itl_ms") or []
    iterations = engine_spans.window_iterations(run)
    return {
        # the traced run's own mean token gap: beside an untraced run's
        # `itl_mean_ms` it says what tracing costs when it is on
        "itl_mean_ms": sum(gaps) / len(gaps) if gaps else None,
        "prompt_free_iterations": len(free),
        "iterations": iterations,
        "iteration_mean_ms": sum(lengths) / n,
        "iteration_p50_ms": percentile(lengths, 50) if lengths else None,
        "span_ms": out,
        "between_ms": sum(total.get(b_, 0.0) for b_ in BETWEEN) * 1e3
        / max(1, iterations),
        "cover": {p: sum(total.get(c, 0.0) for c in cs) / total[p]
                  for p, cs in SUB_SPANS.items() if total.get(p)},
    }


def main() -> int:
    inner = harness.layer_metrics

    def and_the_account(run):
        if run.spans is not None:
            harness.say("account " + json.dumps(account(run)))
            idle = host_share.idle_by_span(run)
            if idle is not None:
                harness.say("idle_by_span " + json.dumps({
                    "traced_iterations": host_share.traced_iterations(run),
                    "seconds": dict(sorted(idle.items(),
                                           key=lambda kv: -kv[1]))}))
        return inner(run)

    harness.layer_metrics = and_the_account
    return bench_run.main(sys.argv[1:] + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
