"""The host's share of a decode iteration, taken apart (PR 38): what the
readers of the engine's sub-spans and of the sampler program's scopes
share.

The engine brackets the parts of its ``decode``, ``sample`` and
``first_token`` spans: ``decode_h2d`` and ``decode_dispatch`` inside
``decode``; ``sample_operands``, ``sample_dispatch`` and ``token_read``
(and ``load_read`` for a family with experts) inside ``sample`` and
``first_token``, told apart by the argument ``path`` (``decode`` or
``prefill``). Two clocks read them:

- the recorder's (``run.spans``, the host's ``perf_counter``) over the
  whole measured window: sums of durations and of arguments an
  iteration, an iteration being ``engine_spans.window_iterations``'s;
- the trace's (``run.planes``) over the traced stretch, where every span
  is also a ``TraceAnnotation`` beside the device's ops: which idle time
  of the device lies under which span. An annotation carries no
  argument, so a span is named there by its PATH, the annotations of its
  thread that hold it, outermost first (``sample/token_read``,
  ``prefill/first_token/token_read``), and an iteration is one
  ``schedule`` annotation.

A reader with nothing to read (no trace, a program from before the
sub-spans or the scopes) returns None, never 0 and never an exception.
Standard library only.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import engine_spans, scopes, xplane

#: the spans of an iteration's decode half; their sub-spans lie inside
#: them, but for the speculative path's ``token_read``, which lies
#: between its ``decode`` and its ``emit``
DECODE_SIDE = ("decode_inputs", "decode", "sample", "emit")
SEAMS = "seams-under-20us"
NO_SPAN = "no-host-span"
SAMPLER_MODULE = "jit__sample"


# -- the recorder's clock -----------------------------------------------------


def _window_spans(run, name: str, path: Optional[str] = None) -> List[tuple]:
    """``(start, end, args)`` of the spans ``name`` that ended inside the
    measured window; with ``path`` only those whose argument says so."""
    if run.spans is None:
        return []
    t0, t1 = run.values["measured_window"]
    return [(a, b, args or {}) for n, a, b, args in list(run.spans.spans)
            if n == name and t0 <= b < t1
            and (path is None or (args or {}).get("path") == path)]


def span_ms_per_iter(run, name: str,
                     path: Optional[str] = None) -> Optional[float]:
    """Host time inside the spans ``name`` (of that ``path``), ms an
    iteration of the measured window."""
    mine = _window_spans(run, name, path)
    iterations = engine_spans.window_iterations(run) if mine else 0
    if not iterations:
        return None
    return sum(b - a for a, b, _ in mine) * 1e3 / iterations


def arg_sums(run, name: str, keys: Sequence[str]) -> Optional[List[float]]:
    """The sums of the arguments ``keys`` over the window's spans
    ``name`` that carry all of them; None where none does."""
    mine = [args for _, _, args in _window_spans(run, name)
            if all(k in args for k in keys)]
    if not mine:
        return None
    return [sum(args[k] for args in mine) for k in keys]


def intake_ms_per_request(run) -> Optional[float]:
    """The runner's ``intake`` spans' time over the requests they handed
    ``engine.submit`` (their ``submitted: {requests}``)."""
    mine = [(b - a, args["submitted"]["requests"])
            for a, b, args in _window_spans(run, "intake")
            if isinstance(args.get("submitted"), dict)]
    requests = sum(n for _, n in mine)
    if not requests:
        return None
    return sum(d for d, _ in mine) * 1e3 / requests


# -- the trace's clock ----------------------------------------------------------


def host_spans(planes, prefix: str) -> List[Tuple[float, float, str]]:
    """``(start, end, path)`` of every annotation of the host planes
    whose name starts with ``prefix``, sorted by start. The path is the
    names, prefix taken off, of the annotations of the same line (a
    thread) that hold it, outermost first, and its own last."""
    out: List[Tuple[float, float, str]] = []
    for hp in xplane.host_planes(planes):
        for ln in hp.lines:
            mine = sorted(((a, b, n[len(prefix):]) for a, b, n in ln.events
                           if n.startswith(prefix)),
                          key=lambda e: (e[0], -e[1]))
            open_: List[Tuple[float, str]] = []  # (end, path) of holders
            for a, b, name in mine:
                while open_ and open_[-1][0] < b:
                    open_.pop()
                path = (open_[-1][1] + "/" if open_ else "") + name
                out.append((a, b, path))
                open_.append((b, path))
    out.sort()
    return out


def _leaf(path: str) -> str:
    return path.rsplit("/", 1)[-1]


def _trace_spans(run) -> List[Tuple[float, float, str]]:
    if run.planes is None or run.spans is None:
        return []
    return host_spans(run.planes, run.spans.prefix)


def _device_window(run) -> Optional[Tuple[float, float]]:
    """The traced window: the first chip's first op to its last (the
    serve cells hold one chip; ``xplane.idle_gaps_by_host_span`` reads the
    first too)."""
    devs = xplane.device_planes(run.planes) if run.planes is not None else []
    if not devs:
        return None
    ops = devs[0].line_events(xplane.OPS_LINE)
    return ops[0][0], max(b for _, b, _ in ops)


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """``{path: seconds}``: the device's idle time inside the traced
    window (first op to last op), every part of it given to the innermost
    host span that covers it, under that span's path. ALL names, no limit:
    the gaps under 20 us, the seams between back-to-back ops, under
    ``SEAMS`` and what no span covers under ``NO_SPAN``, so the values add
    up to the window's idle time. The split is
    ``xplane.idle_gaps_by_host_span``'s own, handed the same device planes
    and ONE host line whose annotations are named by their paths."""
    spans = _trace_spans(run)
    if not spans or _device_window(run) is None:
        return None
    prefix = run.spans.prefix
    by_path = xplane.Plane("/host:paths", [xplane.Line(
        "spans", [(a, b, prefix + path) for a, b, path in spans])])
    return dict(xplane.idle_gaps_by_host_span(
        xplane.device_planes(run.planes) + [by_path], prefix,
        limit=len(spans) + 2))


def traced_iterations(run) -> int:
    """The iterations of the traced stretch: the ``schedule`` annotations
    (one an iteration) that ended between the device's first op and its
    last."""
    window = _device_window(run)
    if window is None:
        return 0
    return sum(1 for _, b, path in _trace_spans(run)
               if _leaf(path) == "schedule" and window[0] <= b < window[1])


def on_decode_path(path: str) -> bool:
    """Whether a span of that path belongs to an iteration's decode half:
    one of ``DECODE_SIDE``, a span inside one, or a ``token_read`` outside
    ``first_token`` (the speculative path's)."""
    parts = path.split("/")
    return (any(p in DECODE_SIDE for p in parts)
            or (parts[-1] == "token_read" and "first_token" not in parts))


def decode_token_read(path: str) -> bool:
    """The decode path's blocking read: a ``token_read`` inside ``sample``
    or, on the speculative path, between ``decode`` and ``emit``."""
    return _leaf(path) == "token_read" and on_decode_path(path)


def idle_ms_per_iter(run, takes: Callable[[str], bool],
                     needs: str) -> Optional[float]:
    """The device's idle time under the spans whose path ``takes``
    accepts, ms an iteration of the traced stretch; None where the trace
    holds no span named ``needs`` (a program from before it)."""
    if not any(_leaf(p) == needs for _, _, p in _trace_spans(run)):
        return None
    by_path = idle_by_span(run)
    iterations = traced_iterations(run) if by_path else 0
    if not iterations:
        return None
    return sum(s for p, s in by_path.items() if takes(p)) * 1e3 / iterations


# -- the sampler program on the device --------------------------------------------


def sampler_ms_per_iter(run, scope: Optional[str] = None) -> Optional[float]:
    """Device time of the sampler program (``jit__sample``, both batch
    shapes: every slot's row a decode step, one row a completed prompt),
    ms an iteration of the traced stretch: with ``scope`` of its ops
    under that ``jax.named_scope`` (``lib/scopes.py``), else of its
    executions on the "XLA Modules" line."""
    if run.planes is None:
        return None
    secs, runs = xplane.needle_seconds(run.planes, [SAMPLER_MODULE],
                                       xplane.MODULES_LINE)
    iterations = traced_iterations(run) if runs else 0
    if not iterations:
        return None
    if scope is None:
        return secs * 1e3 / iterations
    an_execution = scopes.scope_ms(run, scope, SAMPLER_MODULE)
    if an_execution is None:
        return None
    return an_execution * runs / iterations
