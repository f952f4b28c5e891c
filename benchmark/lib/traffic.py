"""The one general generator of serving traffic. A mix is a data file of
parameters (``benchmark/traffic/<mix>.json``); nothing here knows a mix by
name.

Every seed gets the SAME multiset of work and of arrival gaps, in another
order: the lengths and the gaps are drawn from the mix's own ``shape_seed``
and only reordered (locally, see ``BLOCK``) by ``--seed``, which also draws
the token ids. Runs with different seeds then differ by rounding and by
order, not by how much work the window holds or when it comes.

  arrival.process  "poisson_trace": ONE draw of exponential gaps (a Poisson
                   process at ``rate_per_s``, from ``shape_seed``), rescaled
                   so that the ramp and the window each hold exactly
                   round(rate * length) requests, replayed by every seed
                   in its own local order: bursty like a Poisson process,
                   but no seed draws its own; "burst": ``count`` requests
                   all due when the window opens (a saturating mix)
  prompt_len, output_len   {"dist": "lognormal", "median", "sigma", "min",
                   "max"} (clipped) or {"dist": "uniform", "min", "max"}
  max_total        prompt + output never exceed it (the cache's ring)
  shared_prefix    {"len": L, "count": K}: the first L tokens of every
                   prompt longer than L are one of K prefixes (sessions
                   sharing a system prompt); absent or K = 0: none shared

``uniform`` and ``shared_prefix`` have no mix yet: they are what the
prefill and sessions cells of PERF.md's Open questions are made of, and a
PR that adds such a cell may add data files only (selftest.py checks both).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Planned:
    due_s: float  # from the start of the ramp
    prompt: List[int]
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        raw = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(raw), spec["min"], spec["max"]).astype(np.int64)


#: The seed reorders requests only within runs of this many consecutive
#: arrivals: every seed then offers the same work in every ~0.7 s stretch
#: (at the chat mix's rate), and what differs between seeds is the order
#: and the tokens, not whether the long answers fall early or late in the
#: window (which alone moved tokens/s by 6% between seeds; my chip runs,
#: PR 23).
BLOCK = 16


def _local_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` that moves nothing out of its block."""
    order = np.arange(n)
    for lo in range(0, n, BLOCK):
        rng.shuffle(order[lo:lo + BLOCK])
    return order


def _part(traffic: dict, n: int, span_s: float, shape: np.random.Generator,
          rng: np.random.Generator) -> tuple:
    """``n`` requests over ``span_s`` seconds: gaps and (prompt, output)
    lengths drawn from ``shape``, then put in the seed's order."""
    gaps = shape.exponential(1.0, n)
    gaps *= span_s / gaps.sum()
    plen = _lengths(traffic["prompt_len"], n, shape)
    olen = _lengths(traffic["output_len"], n, shape)
    olen = np.maximum(1, np.minimum(olen, traffic["max_total"] - plen))
    order = _local_order(n, rng)
    return np.cumsum(gaps[_local_order(n, rng)]), plen[order], olen[order]


def open_loop_plan(traffic: dict, seed: int, seconds: float,
                   vocab: int) -> List[Planned]:
    """The ramp and the window are drawn apart, so that every seed has the
    same requests DUE IN THE WINDOW (and the same in the ramp), in another
    order: with one pool for both, the seed decided how many fell inside
    the window (651-683 of them, and tokens/s followed; my chip runs,
    PR 23)."""
    arr = traffic["arrival"]
    ramp = float(arr.get("ramp_s", 0.0))
    shape = np.random.default_rng(traffic["shape_seed"])
    rng = np.random.default_rng(seed)
    if arr["process"] == "poisson_trace":
        rate = arr["rate_per_s"]
        parts = [(0.0, round(rate * ramp), ramp),
                 (ramp, max(1, round(rate * seconds)), seconds)]
    elif arr["process"] == "burst":
        parts = [(ramp, int(arr["count"]), 0.0)]
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    due, plen, olen = [], [], []
    for start, n, span in parts:
        if n:
            d, p, o = _part(traffic, n, span, shape, rng)
            # the last request of a part is due just inside it, not on the
            # boundary (a window is [t0, t1))
            due.append(start + d * (1.0 - 1e-9))
            plen.append(p)
            olen.append(o)
    due, plen, olen = map(np.concatenate, (due, plen, olen))
    ids = rng.integers(0, vocab, size=int(plen.sum()), dtype=np.int64)
    prompts = np.split(ids, np.cumsum(plen)[:-1])
    shared = traffic.get("shared_prefix") or {}
    if shared.get("count"):
        L = shared["len"]
        prefixes = rng.integers(0, vocab, size=(shared["count"], L))
        for i, p in enumerate(prompts):
            if len(p) > L:
                p[:L] = prefixes[i % shared["count"]]
    return [Planned(float(d), p.tolist(), int(o))
            for d, p, o in zip(due, prompts, olen)]
