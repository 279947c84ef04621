"""Recompile-hazard pass: statically enumerate the program set a
serving call site can produce.

The engine's only step functions are ``serving_tick`` (decode tokens +
prompt spans as one program; geometry rides in device arrays) and
``serving_tick_block`` (the fused decode block), jitted over
``models/serving_tick.py``'s two functions and a family's record. The
compiled-program key is the packed token width, and the reachable set
is fixed by construction: mixed widths run the tail/no-tail tick pair,
width ``S`` exactly ONE program (the fused block — sampling rides it as
data). ``enumerate_tick_programs`` enumerates that set so the invariant
— ≤ 2 programs per width bucket — is *proven* from engine dispatch,
not asserted, and any future dispatch change that silently multiplies
the set fails the pass (and warns at engine construction) before
traffic does.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Set

from .framework import (Finding, GraphTarget, LintPass, Severity,
                        register_pass)

__all__ = ["ServingGeometry", "enumerate_tick_programs",
           "program_inventory",
           "tick_budget", "tick_width_grid", "RecompileHazardPass"]


@dataclass
class ServingGeometry:
    """The host-side facts that determine the serving program set."""
    page_size: int
    pages_per_slot: int
    buckets: List[int]          # prompt-length buckets (sorted)
    prefill_chunk: Optional[int] = None
    max_batch: int = 0
    decode_block: int = 1
    # speculative decoding (r15): draft-length cap; > 0 routes every
    # span-carrying tick through the ONE verify program per width
    spec_k: int = 0

    @staticmethod
    def of_engine(engine) -> "ServingGeometry":
        """Extract the geometry from a live ``ServingEngine``."""
        return ServingGeometry(
            page_size=engine.pool.page_size,
            pages_per_slot=engine.scheduler.pages_per_slot,
            buckets=list(engine._buckets),
            prefill_chunk=engine._chunk,
            max_batch=engine.scheduler.max_batch,
            decode_block=engine._decode_block,
            spec_k=engine._spec_k)


def tick_budget(geom: ServingGeometry) -> int:
    """The ragged engine's per-tick prefill token budget: the
    prefill_chunk when set, else a whole max-length suffix (the same
    arithmetic as ``ServingEngine.__init__``)."""
    return (int(geom.prefill_chunk) if geom.prefill_chunk is not None
            else int(geom.buckets[-1]))


def tick_width_grid(geom: ServingGeometry) -> List[int]:
    """The engine's packed-width grid (the same arithmetic as
    ``ServingEngine.__init__`` — pinned against a live engine by
    test): prompt buckets capped at the prefill budget, plus the
    budget itself; a speculative geometry adds the all-slots-drafting
    width ``S*(1+spec_k)`` and the combined worst case on top, so
    every reachable span-token total (prefill spans + draft spans)
    snaps to a small static set."""
    budget = tick_budget(geom)
    grid = {min(int(b), budget) for b in geom.buckets} | {budget}
    if geom.spec_k:
        spec_max = int(geom.max_batch) * (1 + int(geom.spec_k))
        grid |= {spec_max, budget + spec_max}
    return sorted(grid)


def enumerate_tick_programs(geom: ServingGeometry) -> Dict[int,
                                                           Set[str]]:
    """Exact reachable ``{packed_width: {program}}`` under the ragged
    engine's dispatch (``ServingEngine._decode_tick``). Since r16
    SAMPLING is per-slot DATA to the fused in-graph sampler
    (temperature/top-k/top-p/keys ride the tick meta), so temperature
    never selects a program:

    * ticks with pending prefill spans run ``serving_tick`` at packed
      width ``max_batch + w`` where ``w`` is the smallest entry of the
      width grid (prompt buckets capped at the budget, plus the budget
      itself) covering the tick's span tokens — span count, span
      offsets, prefix size and cache lengths are all device data.
      Each width compiles with the fused decode tail
      (``decode_tail = decode_block-1``; sampling slots ride it via
      the fused sampler) plus, when ``decode_block > 1``, the
      tail-less variant for ticks where NO slot is tail-live (pure
      mid-prefill ticks): at most two compiles per width;
    * pure-decode ticks — greedy, sampling or mixed — run the fused
      ``serving_tick_block`` at width ``max_batch``. The pre-r16
      width-S single-step sampling ``serving_tick[decode]`` program
      is GONE from the inventory.

    A SPECULATIVE geometry (``spec_k > 0``) changes the mixed widths,
    not the bound: every tick carrying spans or drafts — prefill-only
    ticks included — runs the ONE ``spec_k``-static verify program for
    its width (speculation replaces the fused decode tail there, so
    the tail variant is unreachable), and the width grid grows the two
    speculative entries (``tick_width_grid``). Width ``max_batch``
    keeps the fused block alone — a slot degraded by the acceptance
    policy, like a sampling slot, is a data state, not a new program.

    Nothing else is reachable, whatever the traffic: the bound is
    1-2 programs per width bucket by construction.
    """
    S = int(geom.max_batch)
    k = int(geom.decode_block)
    grid = tick_width_grid(geom)
    if geom.spec_k:
        mixed: Set[str] = {f"serving_tick[verify,spec_k="
                           f"{int(geom.spec_k)}]"}
    else:
        mixed = {f"serving_tick[mixed,tail={k - 1}]"}
        if k > 1:
            # reachable only on ticks with zero tail-live slots (all
            # spans mid-prefill): the engine drops the tail there
            # rather than run k-1 all-dead steps
            mixed.add("serving_tick[mixed,tail=0]")
    out: Dict[int, Set[str]] = {S + w: set(mixed) for w in grid}
    out[S] = {f"serving_tick_block[k={k}]"}
    return out


def program_inventory(geom: ServingGeometry) -> Dict[str, object]:
    """The one schema for "what programs may this engine compile":
    ``{programs_per_bucket, total, widths: {str(width): [program]}}``.
    Shared by ``graph_lint --json`` (``serving_programs`` and the
    ``observability`` block), the engine-ctor warning, and the runtime
    recompile sentinel (observability/sentinel.py) — the static proof
    and the runtime alarm carry the SAME inventory, so a CI consumer
    and a production postmortem can be diffed field for field."""
    programs = enumerate_tick_programs(geom)
    return {
        "programs_per_bucket": max(
            (len(v) for v in programs.values()), default=0),
        "total": sum(len(v) for v in programs.values()),
        "widths": {str(w): sorted(v)
                   for w, v in sorted(programs.items())},
    }


@register_pass
class RecompileHazardPass(LintPass):
    """Runs on targets whose ``meta['geometry']`` is a
    :class:`ServingGeometry` (the CLI attaches the flagship engines');
    jaxpr-free — the hazard is host-side dispatch, not graph content.
    Geometries are held to ``ragged_limit`` (the one-program-tick
    invariant: ≤ 2 per width bucket)."""

    name = "recompile-hazard"

    def __init__(self, ragged_limit: int = 2):
        self.ragged_limit = int(ragged_limit)

    def run(self, target: GraphTarget) -> List[Finding]:
        geom = target.meta.get("geometry")
        if geom is None:
            return []
        findings: List[Finding] = []
        programs = enumerate_tick_programs(geom)
        for width in sorted(programs):
            progs = programs[width]
            if len(progs) > self.ragged_limit:
                findings.append(self.finding(
                    target,
                    f"tick width {width} reaches {len(progs)} distinct "
                    f"programs ({sorted(progs)}) > limit "
                    f"{self.ragged_limit}: each is an XLA compile "
                    f"inside the serving tick — the one-program-tick "
                    f"dispatch regressed"))
        worst = max((len(v) for v in programs.values()), default=0)
        inventory = {w: sorted(v) for w, v in sorted(programs.items())}
        findings.append(self.finding(
            target,
            f"program inventory (ragged tick): {inventory} — proven "
            f"bound {worst} programs/bucket (limit {self.ragged_limit})",
            severity=Severity.INFO))
        return findings
