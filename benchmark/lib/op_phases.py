"""The device's time by the program's phase scopes (PR 24).

The program wraps the phases of its models and of its step in
``jax.named_scope`` (``embed``, ``attn_norm``, ``attn``, ``ffn_norm``,
``ffn``, ``lm_head_loss`` / ``lm_head``, ``grad_norm_clip``, ``optimizer``;
in the decode program ``kv_write`` inside ``attn`` and ``kv_merge`` after
the layers; ``sampler``). A scope
reaches the ``op_name`` of every instruction traced under it
(``jit(step)/transpose(jvp(ffn))/dot_general``). A chip trace does NOT
carry that in the event's name, which is the instruction's text, but as
the ``tf_op`` stat of the event's METADATA entry, which ``lib/xplane.py``
skips (my chip run, PR 24). So this module reads the run's trace file
once more, for that stat alone; the events are those ``lib/xplane.py``
parsed (``run.planes``), matched by the metadata's name.

Time is given to the op that started last among those running (a
``while`` or a ``cond`` spans the ops of its body: summed durations would
count the body twice), so the parts of a program add up to the union of
its ops' intervals. Standard library only.

Field numbers (xplane.proto) beyond those ``lib/xplane.py`` lists:
  XPlane: stat_metadata=5 (map: key=1, value=2 XStatMetadata{id=1 name=2})
  XEventMetadata: stats=5   XStat: metadata_id=1 str_value=5 bytes_value=6
  ref_value=7 (the id of a stat_metadata entry whose NAME is the string)
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from typing import Dict, Optional, Sequence, Tuple

from . import harness, xplane

#: the scopes the program sets; a path component is one of them only as a
#: whole word, the autodiff and vmap wrappers taken off
PHASES = ("embed", "attn_norm", "attn", "ffn_norm", "ffn", "lm_head_loss",
          "lm_head", "grad_norm_clip", "optimizer", "kv_write", "kv_merge",
          "sampler")
OP_NAME_STAT = "tf_op"
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+:?$|:$")


def phase_of(op_path: str) -> Optional[str]:
    """The innermost phase scope of an ``op_name`` path, or None:
    ``jit(_decode)/attn/kv_write/dynamic_update_slice`` -> ``kv_write``,
    ``jit(step)/transpose(jvp(ffn))/dot_general:`` -> ``ffn``."""
    for part in reversed(op_path.split("/")):
        part = _WRAPPERS.sub("", part)
        if part in PHASES:
            return part
    return None


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _plane_phases(buf) -> Tuple[str, Dict[str, str]]:
    """``(plane name, {event name: phase})`` of one XPlane, for the events
    whose metadata carries an ``op_name`` with a phase scope in it."""
    name, metas, stat_names = "", [], {}
    for fno, wt, v in xplane._fields(buf):
        if wt != 2:
            continue
        if fno == 2:
            name = _text(v)
        elif fno == 4:
            metas.append(v)
        elif fno == 5:
            key, sname = 0, ""
            for f1, w1, v1 in xplane._fields(v):
                if f1 == 1 and w1 == 0:
                    key = v1
                elif f1 == 2 and w1 == 2:
                    for f2, w2, v2 in xplane._fields(v1):
                        if f2 == 2 and w2 == 2:
                            sname = _text(v2)
            stat_names[key] = sname
    phases: Dict[str, str] = {}
    for entry in metas:
        for f1, w1, meta in xplane._fields(entry):
            if not (f1 == 2 and w1 == 2):
                continue
            ename, path = "", ""
            for fno, wt, v in xplane._fields(meta):
                if fno == 2 and wt == 2:
                    ename = _text(v)
                elif fno == 5 and wt == 2:
                    stat, value = 0, ""
                    for f2, w2, v2 in xplane._fields(v):
                        if f2 == 1 and w2 == 0:
                            stat = v2
                        elif f2 in (5, 6) and w2 == 2:
                            value = _text(v2)
                        elif f2 == 7 and w2 == 0:
                            value = stat_names.get(v2, "")
                    if stat_names.get(stat) == OP_NAME_STAT:
                        path = value
            phase = phase_of(path)
            if phase is not None:
                phases[ename] = phase
    return name, phases


def innermost_seconds(events: Sequence[Tuple[float, float, str]]
                      ) -> Dict[str, float]:
    """``{name: seconds}`` over ``events`` (start, end, name): every
    instant that some event covers goes to the one that started last
    among those running then (of two that start together, the shorter)."""
    out: Dict[str, float] = {}
    running: list = []  # heap of (-start, -position, end, name)
    t, last = 0.0, float("inf")
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    for i, (a, b, name) in enumerate(ordered + [(last, last, "")]):
        # hand out the time up to this start
        while running and t < a:
            _, _, end, k = running[0]
            if end <= t:
                heapq.heappop(running)
                continue
            stop = min(end, a)
            out[k] = out.get(k, 0.0) + (stop - t)
            t = stop
        if a == last:
            break
        t = max(t, a)
        heapq.heappush(running, (-a, -i, b, name))
    return out


def trace_file(run) -> Optional[str]:
    """This run's ``xplane.pb``: the newest under the cell's trace
    directories (``harness.Profile`` names them ``<cell>-<seed>`` and
    replaces one a run)."""
    tag = re.compile(re.escape(run.cell.name) + r"-\d+$")
    paths = [p for p in glob.glob(os.path.join(
        harness.OUT_DIR, "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb")) if tag.match(p.split(os.sep)[-5])]
    return max(paths, key=os.path.getmtime) if paths else None


# one trace at a time: ((file, mtime), {plane: {event name: phase}},
# {(plane, module): ({event name: seconds}, executions)})
_TRACE: list = [None, {}, {}]


def _phases_by_plane(run) -> Dict[str, Dict[str, str]]:
    path = trace_file(run)
    if path is None:
        return {}
    key = (path, os.path.getmtime(path))
    if _TRACE[0] != key:
        with open(path, "rb") as f:
            data = memoryview(f.read())
        _TRACE[:] = [key, dict(_plane_phases(v)
                               for fno, wt, v in xplane._fields(data)
                               if fno == 1 and wt == 2), {}]
    return _TRACE[1]


def _self_seconds(plane, module: Optional[str]
                  ) -> Tuple[Dict[str, float], int]:
    """``({event name: seconds}, executions)`` of one chip: its ops' time,
    each instant given to the innermost running op; with ``module`` only of
    the ops that start inside an execution of a program whose name holds
    it, and the number of those executions."""
    if (plane.name, module) not in _TRACE[2]:
        ops, runs = plane.line_events(xplane.OPS_LINE), []
        if module is not None:
            runs = [(a, b) for a, b, n in plane.line_events(xplane.MODULES_LINE)
                    if module in xplane.op_name(n)]
            starts = [a for a, _ in runs]

            def inside(a: float) -> bool:
                i = bisect.bisect_right(starts, a) - 1
                return i >= 0 and a < runs[i][1]

            ops = [e for e in ops if inside(e[0])]
        _TRACE[2][plane.name, module] = (innermost_seconds(ops), len(runs))
    return _TRACE[2][plane.name, module]


def phase_ms(run, phases: Sequence[Optional[str]], *,
             pallas: Optional[bool] = None,
             module: Optional[str] = None) -> Optional[float]:
    """Device time, ms a traced step, of the ops whose innermost phase
    scope is one of ``phases`` (None among them: the ops under no scope), each
    instant given to the innermost running op, mean over the chips.
    ``pallas`` False leaves the Pallas kernels out (they have metrics of
    their own), True takes only them. With ``module`` only the ops inside
    the executions of the programs whose name holds it are taken, and a
    step is one such execution; otherwise a step is one of
    ``run.values["trace_steps"]``. None where there is no trace or where
    no op of it carries a phase scope (a program from before the scopes):
    never 0 for "nothing there"."""
    if run.planes is None:
        return None
    by_plane = _phases_by_plane(run)
    chips = xplane.device_planes(run.planes)
    if not chips or not any(by_plane.get(c.name) for c in chips):
        return None
    secs, steps = 0.0, 0.0
    for c in chips:
        phase = by_plane.get(c.name, {})
        by_op, executions = _self_seconds(c, module)
        steps += executions
        secs += sum(s for name, s in by_op.items()
                    if phase.get(name) in phases
                    and pallas in (None, xplane.PALLAS_TARGET in name))
    if module is None:
        steps = run.values.get("trace_steps", 0) * len(chips)
    if not steps:
        return None
    return secs * 1e3 / steps


def read_declared(run, metric: str) -> Optional[float]:
    """:func:`phase_ms` with what ``layer_metrics/<metric>.json`` declares
    under ``source``: ``phases`` (a list; null in it stands for the ops
    under no scope) and, where given, ``pallas`` and ``module``."""
    src = harness.load_json("layer_metrics", metric + ".json")["source"]
    return phase_ms(run, src["phases"], pallas=src.get("pallas"),
                    module=src.get("module"))
