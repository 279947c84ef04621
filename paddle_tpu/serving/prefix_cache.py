"""Refcounted prefix cache over the paged KV pool.

Reference capability: cross-request KV reuse in paged-attention serving
stacks (Ragged Paged Attention, PAPERS.md; vLLM-style automatic prefix
caching): requests sharing a prompt prefix — system prompts, few-shot
headers — attach the SAME physical KV pages instead of recomputing the
prefix, so admission prefills only the uncached suffix.

Design:

- **Granularity: full pages.** A cached unit is one FULL KV page
  (``page_size`` token positions, all layers — the pool is
  layer-stacked, one page id covers every layer). Full pages are
  immutable after prefill (decode appends at ``position >= prompt_len``,
  which page-aligned sharing keeps out of shared pages), so sharing
  them is write-safe by construction.

- **Keying: a trie keyed by page token tuples.** Node children map
  ``tuple(page's tokens) -> child``; looking a chain up hashes one
  page's tokens per step with the parent's identity carrying the rest
  of the chain — a rolling keying of the token chain. Because dict
  equality compares the actual tuples, a hash collision can never alias
  two different prefixes (the engine's byte-exactness bar).

- **Refcounts + LRU eviction.** ``refs`` counts live requests whose
  page table contains the node's page. Nodes stay cached at zero refs
  and are evicted LRU-first under page pressure (``evict``), but only
  LEAF nodes: an interior node's children attend to its positions, so
  freeing a parent first would dangle the chain. Evicting a leaf
  exposes its parent as the next candidate.

- **Match cap: at most ``floor((n-1)/page_size)`` pages.** At least one
  suffix token is always left to prefill — the engine needs a fresh
  forward pass to take first-token logits from — and the partially
  filled tail page is therefore always request-PRIVATE: the cap is the
  copy-on-write for the tail page (its cache-covered tokens are
  recomputed into a private page rather than shared), which is what
  lets decode append into it without touching shared state and keeps
  outputs bitwise-identical to ``generate()``.

Single-threaded by design: only the engine worker calls mutating
methods (the engine serializes them under its tick lock).
"""
from __future__ import annotations

import heapq
import itertools
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

__all__ = ["PrefixCache", "ColdTier", "prefix_fingerprints"]

# Rolling-hash base/mask for the fleet affinity signal: a chain's
# fingerprint is a polynomial hash over its concatenated page token
# tuples, extended one page at a time (the same rolling keying the trie
# itself uses, collapsed to one int). Fingerprints only ROUTE requests
# (serving/fleet/router.py) — a collision can at worst send a request
# to a colder replica, never alias KV: attachment still goes through
# the trie's exact tuple comparison.
_FP_MUL = 1000003
_FP_MASK = (1 << 64) - 1


def _fp_extend(fp: int, toks) -> int:
    for t in toks:
        fp = (fp * _FP_MUL + int(t) + 1) & _FP_MASK
    return fp


def prefix_fingerprints(prompt, page_size: int, max_depth: int = 2):
    """Rolling-hash fingerprints of ``prompt``'s leading full pages:
    ``[fp(page0), fp(page0+page1), ...]`` up to ``max_depth`` entries,
    capped at the pages a ``PrefixCache`` could ever attach for this
    prompt (``(n-1)//page_size`` — at least one suffix token always
    prefills). The fleet router hashes an incoming prompt with THIS
    function and matches against each replica's
    :meth:`PrefixCache.affinity_summary` — same hash, same page
    framing, so a match means the replica's trie holds that exact
    chain (modulo 64-bit collisions, which only cost routing warmth,
    never correctness)."""
    ps = int(page_size)
    n = len(prompt)
    pages = min(max(0, (int(n) - 1) // ps), int(max_depth))
    out, fp = [], 0
    for i in range(pages):
        fp = _fp_extend(fp, prompt[i * ps:(i + 1) * ps])
        out.append(fp)
    return out


class ColdTier:
    """Bounded host-RAM store for evicted-but-warm KV pages.

    Device page pressure evicts refcount-0 chains from the trie; with a
    cold tier configured (``ServingEngine(cold_tier_bytes=N)``) each
    evicted page's KV is pulled to host memory HERE instead of being
    discarded, keyed by the chain fingerprint up to that page — the
    same rolling hash the fleet router and the migration protocol use.
    A later prompt whose warm trie match ends where a cold chain begins
    re-adopts the pages (alloc + scatter, engine ``_rewarm_cold``)
    instead of recomputing prefill, bitwise-equal to a warm hit: the
    bytes stored are the bytes the device computed.

    LRU by BYTES: ``put`` drops least-recently-touched entries until
    the new entry fits; an entry larger than the whole budget is
    refused. Correctness never depends on the fingerprint key — every
    entry carries its page's exact token tuple and the rewarm path
    verifies it against the prompt before adopting (a 64-bit collision
    costs a missed rewarm, never aliased KV).

    Single-threaded like the trie (engine tick lock serializes all
    calls)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        # chain-fp -> {"toks", "k", "v", "nbytes"} in LRU order
        self._by_fp: "OrderedDict[int, dict]" = OrderedDict()
        self.bytes = 0
        self.spills = 0       # pages paged out to host
        self.hits = 0         # pages re-adopted from host
        self.drops = 0        # pages LRU-dropped to fit the budget

    def __len__(self) -> int:
        return len(self._by_fp)

    def put(self, fp: int, toks: tuple, k, v) -> bool:
        """Store one evicted page's KV under its chain fingerprint;
        returns False when it can never fit the budget."""
        nbytes = int(k.nbytes) + int(v.nbytes)
        if nbytes > self.max_bytes:
            return False
        old = self._by_fp.pop(int(fp), None)
        if old is not None:
            self.bytes -= old["nbytes"]
        while self._by_fp and self.bytes + nbytes > self.max_bytes:
            _, dropped = self._by_fp.popitem(last=False)
            self.bytes -= dropped["nbytes"]
            self.drops += 1
        self._by_fp[int(fp)] = {"toks": tuple(toks), "k": k, "v": v,
                                "nbytes": nbytes}
        self.bytes += nbytes
        self.spills += 1
        return True

    def get(self, fp: int) -> Optional[dict]:
        """Peek (and LRU-touch) one entry; None when absent."""
        ent = self._by_fp.get(int(fp))
        if ent is not None:
            self._by_fp.move_to_end(int(fp))
        return ent

    def pop(self, fp: int) -> Optional[dict]:
        """Remove one entry (the rewarm path pops what it adopted —
        the KV is back on device, holding the host copy would double
        the footprint and go stale if decode extends the chain)."""
        ent = self._by_fp.pop(int(fp), None)
        if ent is not None:
            self.bytes -= ent["nbytes"]
            self.hits += 1
        return ent

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._by_fp), "bytes": self.bytes,
                "max_bytes": self.max_bytes, "spills": self.spills,
                "hits": self.hits, "drops": self.drops}


class _Node:
    __slots__ = ("toks", "parent", "children", "page", "refs",
                 "last_used", "hits")

    def __init__(self, toks, parent, page: int, tick: int):
        self.toks = toks                    # this page's token tuple
        self.parent = parent
        self.children: Dict[tuple, "_Node"] = {}
        self.page = int(page)
        self.refs = 0
        self.last_used = tick
        self.hits = 0                       # acquire() attachments

    def __repr__(self):  # debugging aid only
        return (f"_Node(page={self.page}, refs={self.refs}, "
                f"children={len(self.children)})")


class PrefixCache:
    """Page-granular prefix registry over one ``PagePool``.

    The pool is shared with the serving scheduler: cached pages remain
    ALLOCATED in the pool (they hold live KV) until ``evict`` frees
    them back. ``defrag_plan``-driven compaction must call ``remap``
    with the same plan applied to the pool arrays.
    """

    def __init__(self, pool):
        self.pool = pool
        self.page_size = int(pool.page_size)
        self._root = _Node((), None, -1, 0)
        self._nodes = set()                 # every cached node
        self._tick = itertools.count(1)
        self.evictions = 0
        # cold-tier hook: when set, evict() calls ``spill(node)`` for
        # every node it is about to free, BEFORE the page returns to
        # the pool — the engine's spill callback gathers the page's KV
        # to host while the pool entry still holds it. A raising spill
        # must not wedge eviction (admission depends on it), so
        # failures are swallowed by the caller side.
        self.spill = None

    # ------------------------------------------------------------ sizing ----
    def nodes(self):
        """Snapshot list of every cached node (audit/debug
        introspection — the paged-KV invariant checker walks these)."""
        return list(self._nodes)

    @property
    def cached_pages(self) -> int:
        return len(self._nodes)

    @property
    def reusable_pages(self) -> int:
        """Cached pages not currently referenced by any live request."""
        return sum(nd.refs == 0 for nd in self._nodes)

    # ------------------------------------------------------------ lookup ----
    def _max_pages(self, n_tokens: int) -> int:
        # never cover the whole prompt: >= 1 token must remain for the
        # suffix prefill (first-token logits + private tail page)
        return max(0, (int(n_tokens) - 1) // self.page_size)

    def _walk(self, prompt, max_pages: int) -> List[_Node]:
        ps = self.page_size
        node, out = self._root, []
        for i in range(max_pages):
            key = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            nxt = node.children.get(key)
            if nxt is None:
                break
            out.append(nxt)
            node = nxt
        return out

    def match_pages(self, prompt) -> int:
        """Non-pinning peek: how many pages ``acquire`` would attach."""
        return len(self._walk(prompt, self._max_pages(len(prompt))))

    def acquire(self, prompt) -> List[_Node]:
        """Longest cached page-aligned prefix of ``prompt``, any page
        count (the attached size reaches the tick as data), with every
        attached node's refcount bumped (pinned against eviction). The
        caller owns one release() per acquire()."""
        nodes = self._walk(prompt, self._max_pages(len(prompt)))
        t = next(self._tick)
        for nd in nodes:
            nd.refs += 1
            nd.last_used = t
            nd.hits += 1
        return nodes

    def release(self, nodes: List[_Node]) -> None:
        """Drop one reference per node (request retirement). Pages stay
        cached at zero refs until evicted under pressure."""
        for nd in nodes:
            nd.refs -= 1
            if nd.refs < 0:
                raise AssertionError(
                    f"prefix-cache refcount underflow on page {nd.page}")

    # ------------------------------------------------------------ insert ----
    def insert(self, prompt, parent_nodes: List[_Node],
               pages: List[int]) -> Tuple[List[_Node], List[int]]:
        """Register a freshly prefilled prompt's full pages.

        ``parent_nodes`` — the chain the request attached at admission
        (possibly empty); ``pages`` — the request's PRIVATE pool pages
        holding prompt tokens ``len(parent_nodes)*ps ..`` in order.
        Only FULL pages are offered (the caller passes
        ``n_prompt // ps - len(parent_nodes)`` of them).

        Returns ``(adopted, still_private)``: adopted nodes now own
        their page (refs=1 for this request — pair with release() at
        retirement); ``still_private`` pages duplicated an existing
        chain entry (a concurrent identical prompt won the race) and
        remain the request's to free. The request's page table keeps
        pointing at its own pages either way — adoption changes
        ownership, never the table."""
        ps = self.page_size
        node = parent_nodes[-1] if parent_nodes else self._root
        start = len(parent_nodes)
        adopted, still_private = [], []
        t = next(self._tick)
        for i, page in enumerate(pages):
            j = start + i
            key = tuple(int(x) for x in prompt[j * ps:(j + 1) * ps])
            existing = node.children.get(key)
            if existing is not None:
                # identical content already cached: keep ours private.
                # The chain continues through the EXISTING node — our
                # next page's KV attends to bit-identical positions.
                still_private.append(int(page))
                node = existing
                continue
            child = _Node(key, node, page, t)
            child.refs = 1
            node.children[key] = child
            self._nodes.add(child)
            adopted.append(child)
            node = child
        return adopted, still_private

    # ---------------------------------------------------------- eviction ----
    def evict(self, want_pages: int) -> int:
        """Free up to ``want_pages`` refcount-0 LEAF pages back to the
        pool, LRU-first; returns how many were freed. Freeing a leaf
        can expose its parent as the next candidate, which is pushed
        onto the same heap — one O(N) candidate scan + O(log N) per
        page, not a full rescan per page (eviction runs inside the
        scheduler's admission path)."""
        freed = 0
        if want_pages <= 0:
            return 0
        heap = [(nd.last_used, id(nd), nd) for nd in self._nodes
                if nd.refs == 0 and not nd.children]
        heapq.heapify(heap)
        while heap and freed < want_pages:
            _, _, nd = heapq.heappop(heap)
            if nd.refs or nd.children or nd not in self._nodes:
                continue  # pinned/extended/evicted since it was pushed
            parent = nd.parent
            if self.spill is not None:
                try:
                    self.spill(nd)
                except Exception:
                    pass    # cold tier is best-effort; eviction isn't
            del parent.children[nd.toks]
            self._nodes.discard(nd)
            self.pool.free([nd.page])
            self.evictions += 1
            freed += 1
            if (parent is not self._root and parent.refs == 0
                    and not parent.children):
                heapq.heappush(heap,
                               (parent.last_used, id(parent), parent))
        return freed

    # -------------------------------------------------------- migration ----
    def chain_by_fingerprint(self, fp: int,
                             max_depth: int = 64) -> List[_Node]:
        """Resolve an affinity fingerprint back to its cached chain:
        the node path (root-side first) whose rolling hash — the same
        :func:`prefix_fingerprints` extension the router matched on —
        equals ``fp``. Empty list when no cached chain hashes to it.
        This is the KV-page migration lookup (fleet/proc/): the
        router's warmth signal names chains by fingerprint, so the
        migration request arrives as a fingerprint and the EXPORT side
        re-derives the exact token tuples + current page ids from the
        trie (post-defrag ``node.page`` ids are the live ids — remap
        already rewrote them). A 64-bit collision can at worst export
        a different chain than intended; the ADOPT side re-keys by the
        exported token tuples, so collisions cost a wasted transfer,
        never KV aliasing."""
        target = int(fp) & _FP_MASK
        stack = [(self._root, 0, 0, [])]
        while stack:
            node, cur, d, path = stack.pop()
            if d >= int(max_depth):
                continue
            for toks, child in node.children.items():
                cfp = _fp_extend(cur, toks)
                cpath = path + [child]
                if cfp == target:
                    return cpath
                stack.append((child, cfp, d + 1, cpath))
        return []

    def adopt_chain(self, tokens: List[tuple], pages: List[int],
                    start: int = 0) -> List[_Node]:
        """Graft an EXTERNALLY prefilled chain into the trie (KV-page
        migration adoption): ``tokens`` is the full chain's page token
        tuples, ``tokens[:start]`` must already be cached here (the
        shared prefix the destination holds), and ``pages`` are this
        pool's freshly allocated pages now holding the KV for
        ``tokens[start:]`` (the caller scattered the exported arrays
        in before calling). New nodes enter at ``refs=0`` — cached and
        evictable, exactly the state a locally prefilled chain reaches
        after its owning request retires — so the pool-ownership
        invariants are indistinguishable from local prefill."""
        node = self._root
        for tt in tokens[:start]:
            node = node.children[tuple(tt)]
        t = next(self._tick)
        out: List[_Node] = []
        for tt, page in zip(tokens[start:], pages):
            key = tuple(int(x) for x in tt)
            child = _Node(key, node, int(page), t)
            node.children[key] = child
            self._nodes.add(child)
            out.append(child)
            node = child
        return out

    def match_chain(self, tokens: List[tuple]) -> int:
        """How many leading page token tuples of ``tokens`` are already
        cached (the adopt side's dedup walk: only the uncached suffix
        needs pages + KV scattered)."""
        return len(self.chain_nodes(tokens))

    def chain_nodes(self, tokens: List[tuple]) -> List[_Node]:
        """The cached node path matching a leading run of ``tokens``
        (root-side first; possibly empty). The chunked-adopt protocol
        PINS these (refs += 1) for the transfer's lifetime so a
        concurrent eviction cannot cut the graft point out from under
        the commit; pair every pin with :meth:`release`."""
        node, out = self._root, []
        for tt in tokens:
            nxt = node.children.get(tuple(int(x) for x in tt))
            if nxt is None:
                break
            out.append(nxt)
            node = nxt
        return out

    def node_fingerprint(self, nd: _Node) -> int:
        """Rolling chain fingerprint of the chain ending at ``nd`` —
        the same hash :func:`prefix_fingerprints` computes for the
        token chain root..nd, and the key the cold tier stores the
        node's page under when it is spilled."""
        toks = []
        while nd is not None and nd.parent is not None:
            toks.append(nd.toks)
            nd = nd.parent
        fp = 0
        for tt in reversed(toks):
            fp = _fp_extend(fp, tt)
        return fp

    # ------------------------------------------------------------ defrag ----
    def remap(self, plan: Dict[int, int]) -> None:
        """Apply a ``PagePool.defrag_plan()`` to every cached node's
        page id (the pool arrays + tables were rewritten by
        ``apply_defrag``)."""
        if not plan:
            return
        for nd in self._nodes:
            nd.page = plan.get(nd.page, nd.page)

    # ---------------------------------------------------------- affinity ----
    def affinity_summary(self, max_depth: int = 2) -> Dict[int, Dict]:
        """The fleet router's warmth signal: ``{fingerprint: {"depth",
        "hits", "refs", "last_used"}}`` for every cached chain up to
        ``max_depth`` pages deep, where ``fingerprint`` is the rolling
        hash :func:`prefix_fingerprints` computes for the same token
        chain. Computed LIVE from the trie on every call — an evicted
        chain vanishes from the summary the moment ``evict`` frees it
        (the affinity signal can never point at evicted KV), and a
        defrag ``remap`` changes only page ids, which the fingerprint
        never sees. ``hits`` counts ``acquire()`` attachments (real
        admissions — ``match_pages`` peeks don't inflate it); ``refs``
        and ``last_used`` let the router prefer chains that are hot
        RIGHT NOW. Depth is bounded (system prompts share their first
        pages), so the walk touches the top of the trie, not every
        cached page."""
        out: Dict[int, Dict] = {}
        frontier = [(self._root, 0, 0)]         # (node, fp, depth)
        while frontier:
            node, fp, d = frontier.pop()
            if d >= max_depth:
                continue
            for toks, child in node.children.items():
                cfp = _fp_extend(fp, toks)
                out[cfp] = {"depth": d + 1, "hits": child.hits,
                            "refs": child.refs,
                            "last_used": child.last_used}
                frontier.append((child, cfp, d + 1))
        return out

    def stats(self) -> Dict[str, int]:
        return {"cached_pages": self.cached_pages,
                "reusable_pages": self.reusable_pages,
                "evictions": self.evictions}
