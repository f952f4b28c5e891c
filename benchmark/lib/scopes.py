"""The device's time under ANY ``jax.named_scope`` of the program, for the
metrics whose scope ``lib/op_phases.py:PHASES`` (a closed tuple) does not
hold: the state-space mixers' ``ssm`` with ``ssm_conv``, ``ssm_scan`` and
``ssm_state`` inside it (PR 28).

``op_phases`` gives an op to its INNERMOST scope among ``PHASES``; an op
under ``ssm`` alone resolves to none there and is booked with the ops under
no scope (``decode_rest_ms_per_step`` in a serve cell), so the phase
metrics still add up to the program's time. Here an op belongs to a scope
if the scope is ANY component of its ``op_name`` path: ``ssm`` holds
``ssm_conv``, ``ssm_state`` and the mixer's projections alike. The time is
counted as ``op_phases`` counts it (each instant to the innermost running
op, inside the executions of one program), from the same trace file.
Standard library only.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from . import harness, op_phases, xplane

# one trace at a time: ((file, mtime), {plane: {event name: op_name path}})
_PATHS: list = [None, {}]


def _plane_paths(buf):
    """``(plane name, {event name: op_name path})`` of one XPlane: the
    walk of ``op_phases._plane_phases``, keeping the path itself."""
    name, metas, stat_names = "", [], {}
    for fno, wt, v in xplane._fields(buf):
        if wt != 2:
            continue
        if fno == 2:
            name = op_phases._text(v)
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
                            sname = op_phases._text(v2)
            stat_names[key] = sname
    paths: Dict[str, str] = {}
    for entry in metas:
        for f1, w1, meta in xplane._fields(entry):
            if not (f1 == 2 and w1 == 2):
                continue
            ename, path = "", ""
            for fno, wt, v in xplane._fields(meta):
                if fno == 2 and wt == 2:
                    ename = op_phases._text(v)
                elif fno == 5 and wt == 2:
                    stat, value = 0, ""
                    for f2, w2, v2 in xplane._fields(v):
                        if f2 == 1 and w2 == 0:
                            stat = v2
                        elif f2 in (5, 6) and w2 == 2:
                            value = op_phases._text(v2)
                        elif f2 == 7 and w2 == 0:
                            value = stat_names.get(v2, "")
                    if stat_names.get(stat) == op_phases.OP_NAME_STAT:
                        path = value
            if path:
                paths[ename] = path
    return name, paths


def in_scope(op_path: str, scope: str) -> bool:
    """Whether ``scope`` is a whole component of the path, the autodiff
    and vmap wrappers taken off: ``jit(_decode)/ssm/ssm_state/mul`` is in
    ``ssm`` and in ``ssm_state``, not in ``ssm_scan``."""
    return any(op_phases._WRAPPERS.sub("", part) == scope
               for part in op_path.split("/"))


def _paths_by_plane(run) -> Dict[str, Dict[str, str]]:
    path = op_phases.trace_file(run)
    if path is None:
        return {}
    key = (path, os.path.getmtime(path))
    if _PATHS[0] != key:
        with open(path, "rb") as f:
            data = memoryview(f.read())
        _PATHS[:] = [key, dict(_plane_paths(v)
                               for fno, wt, v in xplane._fields(data)
                               if fno == 1 and wt == 2)]
    return _PATHS[1]


def scope_ms(run, scope: str, module: str) -> Optional[float]:
    """Device time, ms an execution of the programs whose name holds
    ``module``, of the ops under ``scope`` (Pallas kernels included), mean
    over the chips. None where there is no trace, no such execution, or no
    op under that scope (a program from before the scope): never 0 for
    "nothing there"."""
    if run.planes is None:
        return None
    op_phases._phases_by_plane(run)  # op_phases' cache follows the file
    by_plane = _paths_by_plane(run)
    secs, executions, found = 0.0, 0, False
    for chip in xplane.device_planes(run.planes):
        paths = by_plane.get(chip.name, {})
        by_op, runs = op_phases._self_seconds(chip, module)
        executions += runs
        for name, s in by_op.items():
            if in_scope(paths.get(name, ""), scope):
                secs, found = secs + s, True
    if not found or not executions:
        return None
    return secs * 1e3 / executions


def read_declared(run, metric: str) -> Optional[float]:
    """:func:`scope_ms` with what ``layer_metrics/<metric>.json`` declares
    under ``source``: ``scope`` and ``module``."""
    src = harness.load_json("layer_metrics", metric + ".json")["source"]
    return scope_ms(run, src["scope"], src["module"])
