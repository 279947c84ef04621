"""The one traffic generator: reads a traffic file's parameters, makes
a request list from ``--seed``.

Every seed gets the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps — the distribution's quantiles at (i + 0.5) / n — in
another order, with other token content: the mix is ONE schedule,
shuffled once from the traffic file's ``order_seed``, and ``--seed``
picks where in that cycle the run starts (a rotation) and what the
tokens are. So two seeds differ in phase and content, never in the
amount of work or in which request follows which, and a run's spread
is the system's, not the draw's.

Traffic file keys: ``loop`` (``open``: arrivals on a schedule at
``rate_rps``; ``closed``: ``clients_per_slot`` x slots clients, each
sending its next request when its last ended), ``arrivals``
(``poisson``: exponential gaps), ``prompt_tokens`` / ``output_tokens``
(``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "fixed", "value"}``), ``shared_prefix`` (``{"groups",
"tokens"}`` or null), ``greedy``.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's mid-quantiles, clipped."""
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in q])
    v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps at mid-quantiles, rescaled to mean
    exactly ``1 / rate``."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (n / g.sum()) / rate


@dataclasses.dataclass
class Req:
    index: int
    due_s: float            # offset from the window's start (open loop)
    prompt: np.ndarray
    max_new_tokens: int
    counted: bool = True    # False: lead-in, sent before the window opens


def build_requests(traffic: dict, seed: int, n: int, vocab: int,
                   rate: float | None = None) -> list:
    """``n`` requests. Open loop: ``due_s`` ascending from gaps at
    ``rate``; closed loop: ``due_s`` is 0 and the order is the order of
    issue."""
    order = seed_rng(int(traffic.get("order_seed", 0)), 1)
    shift = int(seed_rng(seed, 1).integers(n))

    def schedule(values):
        return np.roll(order.permutation(values), shift)
    p_len = schedule(quantile_lengths(traffic["prompt_tokens"], n))
    o_len = schedule(quantile_lengths(traffic["output_tokens"], n))
    if traffic["loop"] == "open":
        if traffic.get("arrivals", "poisson") != "poisson":
            raise ValueError("only poisson arrivals are implemented")
        gaps = schedule(quantile_gaps(rate, n))
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.zeros((n,))
    content = seed_rng(seed, 2)
    shared = traffic.get("shared_prefix")
    heads = None
    if shared:
        heads = [content.integers(0, vocab, (int(shared["tokens"]),),
                                  dtype=np.int32)
                 for _ in range(int(shared["groups"]))]
    reqs = []
    for i in range(n):
        toks = content.integers(0, vocab, (int(p_len[i]),), dtype=np.int32)
        if heads is not None:
            head = heads[i % len(heads)][:max(int(p_len[i]) - 1, 0)]
            toks[:head.size] = head
        reqs.append(Req(i, float(due[i]), toks, int(o_len[i])))
    return reqs


def lead_in(reqs: list, traffic: dict, seed: int, vocab: int,
            cycle_s: float, lead_s: float) -> list:
    """The end of the same cycle, replayed before the window opens: the
    schedule is cyclic, so the requests due in the last ``lead_s`` of
    the cycle arrive again at negative offsets, with other tokens. They
    load the system to its steady state and are not counted."""
    content = seed_rng(seed, 4)
    out = []
    for r in reqs:
        due = r.due_s - cycle_s
        if due >= -lead_s:
            toks = content.integers(0, vocab, (r.prompt.size,),
                                    dtype=np.int32)
            out.append(Req(r.index - len(reqs), due, toks,
                           r.max_new_tokens, counted=False))
    return out


def open_loop_count(rate: float, seconds: float) -> int:
    return max(int(math.floor(rate * seconds)), 1)
