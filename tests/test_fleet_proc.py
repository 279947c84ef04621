"""Multi-process fleet (paddle_tpu/serving/fleet/proc/): launcher,
RPC transport, crash supervision, KV-page migration.

Correctness bar (ISSUE r16): the process boundary must be INVISIBLE to
a request's math — every stream a worker process serves equals a
standalone in-process ``generate()`` token-for-token, including across
a SIGKILLed worker (crash detect -> hand-back -> re-dispatch, with
exactly-once emission) and across KV-page migration (prefill on A,
adopt on B, decode on B bitwise-equal).

All workers are forced ``JAX_PLATFORMS=cpu`` (WorkerSpec default) and
every test runs under a hard SIGALRM timeout so a hung worker fails
the test instead of wedging tier-1.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.fleet.proc import (ProcServingFleet,
                                           TransportError,
                                           TransportTimeout,
                                           WorkerSpec, WorkerTransport,
                                           request_from_wire,
                                           request_to_wire)
from paddle_tpu.serving.prefix_cache import prefix_fingerprints
from paddle_tpu.serving.scheduler import Request, RequestHandle

# no pytest-timeout in the image: a hard SIGALRM per test is the
# wedge-proofing — a hung worker (or a deadlocked transport) raises
# here instead of stalling the whole tier-1 run
_HARD_TIMEOUT_S = 240


@pytest.fixture(autouse=True)
def _hard_timeout():
    def _boom(signum, frame):
        raise TimeoutError(
            f"fleet-proc test exceeded hard {_HARD_TIMEOUT_S}s limit")
    old = signal.signal(signal.SIGALRM, _boom)
    signal.alarm(_HARD_TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


CFG_KW = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=128,
              dtype="float32", use_flash_attention=False, remat=False)
ENGINE_KW = dict(max_batch=4, page_size=4, max_prompt_len=16,
                 max_new_tokens_cap=16)
SPEC = WorkerSpec("cpu", cfg_kw=CFG_KW, params_seed=0, engine_kw=ENGINE_KW,
                  warm=False)
CFG = L.LlamaConfig(**{**CFG_KW, "dtype": jnp.float32})


@pytest.fixture(scope="module")
def params():
    return L.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref_engine(params):
    eng = ServingEngine(params, CFG, **ENGINE_KW)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def fleet():
    """ONE 2-worker fleet shared by the whole module (spawn + engine
    build is the expensive part); the kill test runs LAST in file
    order and consumes it."""
    f = ProcServingFleet(SPEC, replicas=2, policy="round_robin")
    yield f
    f.close()


# ---------------------------------------------------------------------------
# wire schema
# ---------------------------------------------------------------------------

def test_request_wire_roundtrip():
    """Request parameters survive the hop; deadlines travel as
    REMAINING seconds (monotonic clocks are per-process)."""
    req = Request([1, 2, 3], 4, eos_token_id=7,
                  deadline_s=time.monotonic() + 5.0,
                  temperature=0.5, top_p=0.9, top_k=3, seed=11)
    w = request_to_wire(req)
    assert w["rid"] == req.id and w["prompt"] == [1, 2, 3]
    assert 0.0 < w["deadline"] <= 5.0
    twin = request_from_wire(w)
    np.testing.assert_array_equal(twin.prompt, req.prompt)
    assert (twin.max_new_tokens, twin.eos_token_id, twin.temperature,
            twin.top_p, twin.top_k, twin.seed) == (4, 7, 0.5, 0.9, 3,
                                                   11)
    assert twin.deadline_s is not None
    # no deadline stays no deadline
    assert request_from_wire(
        request_to_wire(Request([1], 1))).deadline_s is None


# ---------------------------------------------------------------------------
# transport unit tests (no process needed: drive the demux directly)
# ---------------------------------------------------------------------------

def _shell_transport():
    """A WorkerTransport shell around the frame demux only."""
    t = object.__new__(WorkerTransport)
    t.name = "shell"
    t._lock = threading.Lock()
    t._waiters = {}
    t._fseq = {}
    t.frame_violations = 0
    t.ready = None
    t._ready_evt = threading.Event()
    t._fatal = None
    got = []
    t.on_frame = got.append
    return t, got


def test_frame_ordering_enforced():
    """Per-request fseq must count 0,1,2,...; an out-of-order frame is
    counted and DROPPED — it can never corrupt a caller's stream."""
    t, got = _shell_transport()
    t._feed(("tok", 1, 0, 10))
    t._feed(("tok", 1, 2, 12))          # gap: violation, dropped
    assert t.frame_violations == 1
    t._feed(("tok", 1, 1, 11))          # in-order resumes
    t._feed(("tok", 1, 1, 11))          # replay: violation, dropped
    assert t.frame_violations == 2
    t._feed(("done", 1, 2, "completed", ""))
    assert [m[0] for m in got] == ["tok", "tok", "done"]
    assert [m[3] for m in got if m[0] == "tok"] == [10, 11]
    # done must carry the final count too
    t._feed(("tok", 2, 0, 5))
    t._feed(("done", 2, 3, "completed", ""))    # wrong count: dropped
    assert t.frame_violations == 3
    assert sum(1 for m in got if m[0] == "done") == 1
    # independent requests keep independent sequences
    t._feed(("tok", 3, 0, 9))
    assert t.frame_violations == 3


def test_frame_reply_resolves_waiter():
    t, _ = _shell_transport()
    ev = threading.Event()
    slot = [ev, None, None]
    t._waiters[7] = slot
    t._feed(("reply", 7, True, {"x": 1}))
    assert ev.is_set() and slot[1] is True and slot[2] == {"x": 1}
    # a reply for a popped (timed-out) waiter is discarded quietly
    t._feed(("reply", 7, True, {"x": 2}))


# ---------------------------------------------------------------------------
# migration mechanics, in-process (engine.export_chain / adopt_chain)
# ---------------------------------------------------------------------------

HEADER = list(range(1, 9))              # 8 tokens = 2 full pages


def _chain_fp(tail):
    prompt = np.asarray(HEADER + tail, np.int32)
    return int(prefix_fingerprints(prompt, 4, max_depth=8)[-1])


def test_engine_export_adopt_bitwise(params, ref_engine):
    """The core migration invariant with no processes in the way:
    prefill on A, export the chain by fingerprint, adopt into B,
    decode on B == single-engine generate(), bitwise."""
    a = ServingEngine(params, CFG, **ENGINE_KW)
    b = ServingEngine(params, CFG, **ENGINE_KW)
    try:
        a.generate(HEADER + [50, 51, 52], 6)
        fp = _chain_fp([50, 51, 52])
        blob = a.export_chain(fp)
        assert blob is not None and blob["page_size"] == 4
        assert [len(t) for t in blob["tokens"]] == [4, 4]
        assert blob["k"].shape[2] == 2      # pages axis
        assert b.adopt_chain(blob) == {"matched_pages": 0,
                                       "adopted_pages": 2}
        # adoption is idempotent: the trie dedups, never double-allocs
        assert b.adopt_chain(blob) == {"matched_pages": 2,
                                       "adopted_pages": 0}
        out = b.generate(HEADER + [60, 61], 6)
        np.testing.assert_array_equal(
            out, ref_engine.generate(HEADER + [60, 61], 6))
        assert b.snapshot()["counters"]["prefix_hits"] >= 1
        # unknown fingerprints export nothing
        assert a.export_chain(987654321) is None
    finally:
        a.close()
        b.close()


def test_engine_export_after_defrag(params, ref_engine):
    """Export must follow the LIVE page ids: scatter the source's page
    table (evict an older chain out from under a newer one), compact
    with defragment(), THEN export — the adopted decode stays
    bitwise-equal because export reads node.page after remap."""
    a = ServingEngine(params, CFG, **ENGINE_KW)
    b = ServingEngine(params, CFG, **ENGINE_KW)
    try:
        other = list(range(100, 108))
        a.generate(other + [9, 8], 6)       # older chain: low pages
        a.generate(HEADER + [50, 51], 6)    # target chain: higher pages
        with a._tick_lock:                  # punch a hole under it
            a.prefix_cache.evict(2)
        moved = a.defragment()
        assert moved >= 1                   # pages actually remapped
        blob = a.export_chain(_chain_fp([50, 51]))
        assert blob is not None
        assert b.adopt_chain(blob)["adopted_pages"] == 2
        out = b.generate(HEADER + [77], 6)
        np.testing.assert_array_equal(
            out, ref_engine.generate(HEADER + [77], 6))
    finally:
        a.close()
        b.close()


def test_adopt_rejects_page_size_mismatch(params):
    a = ServingEngine(params, CFG, **ENGINE_KW)
    c = ServingEngine(params, CFG,
                      **{**ENGINE_KW, "page_size": 8})
    try:
        a.generate(HEADER + [50], 6)
        blob = a.export_chain(_chain_fp([50]))
        assert blob is not None
        with pytest.raises(ValueError, match="page-size mismatch"):
            c.adopt_chain(blob)
    finally:
        a.close()
        c.close()


# ---------------------------------------------------------------------------
# live fleet: parity, refusal, timeout, migration — then the kill
# ---------------------------------------------------------------------------

def test_proc_fleet_bitwise_parity(fleet, ref_engine):
    """Mixed requests over 2 worker processes: every stream equals the
    single in-process engine token-for-token (same weights by
    params_seed), and the merged scrape carries both workers."""
    rng = np.random.RandomState(0)
    specs = [(rng.randint(1, 256,
                          (int(rng.randint(2, 12)),)).tolist(),
              int(rng.randint(2, 10))) for _ in range(8)]
    handles = [fleet.submit(p, m) for p, m in specs]
    outs = [h.result(timeout=180) for h in handles]
    for (p, m), out in zip(specs, outs):
        np.testing.assert_array_equal(out, ref_engine.generate(p, m))
    snap = fleet.snapshot()
    served = {n: h["counters"]["completed"]
              for n, h in snap["replicas"].items() if "counters" in h}
    assert sum(served.values()) >= len(specs)
    assert all(v > 0 for v in served.values())   # round-robin spread
    text = fleet.expose()
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert len(types) == len(set(types))         # one TYPE per family
    assert 'replica="w0"' in text and 'replica="w1"' in text
    assert "paddle_serving_fleet_generation" in text


def test_oversized_prompt_refused_not_crashed(fleet):
    """A prompt beyond the worker's geometry is REFUSED over the
    transport (inject -> accepted:False -> router RuntimeError), and
    the worker stays alive."""
    r0 = fleet.replicas()[0]
    big = Request(list(range(1, 31)), 4)        # 30 > max_prompt_len 16
    assert r0.inject(big) is False
    with pytest.raises(RuntimeError, match="no serving replica"):
        fleet.submit(list(range(1, 31)), 4)
    assert r0.serving and r0.alive


def test_never_ack_worker_times_out(fleet):
    """A worker that never ACKs (SIGSTOPped) raises TransportTimeout
    instead of wedging the caller; after SIGCONT the same transport
    serves rpcs again (the late reply is discarded quietly)."""
    rep = fleet.replicas()[1]
    os.kill(rep.pid, signal.SIGSTOP)
    try:
        with pytest.raises(TransportTimeout):
            rep._rpc("ping", timeout=1.0)
    finally:
        os.kill(rep.pid, signal.SIGCONT)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            assert rep._rpc("ping", timeout=5.0)["pid"] == rep.pid
            break
        except TransportError:
            time.sleep(0.2)
    else:
        pytest.fail("worker did not recover after SIGCONT")


def test_unknown_op_is_an_error_not_a_hang(fleet):
    with pytest.raises(TransportError, match="unknown op"):
        fleet.replicas()[0]._rpc("no_such_op", timeout=10.0)


def test_kv_migration_between_workers(fleet, ref_engine):
    """Prefill on worker A -> migrate the chain's KV pages by trie
    fingerprint -> decode on worker B: B's stream is bitwise-equal to
    the single-engine run, and B's prefix cache scores real hits."""
    r0, r1 = fleet.replicas()[:2]
    header = list(range(1, 9))                  # 8 tokens = 2 pages
    warm = Request(header + [50, 51, 52], 6)
    assert r0.inject(warm)
    RequestHandle(warm).result(timeout=180)
    fps = prefix_fingerprints(np.asarray(header + [50, 51, 52],
                                         np.int32), 4, max_depth=8)
    before = (r1.snapshot_dict() or {}).get("counters", {})
    stats = fleet.migrate_chain(int(fps[-1]), r0.name, r1.name)
    assert stats == {"matched_pages": 0, "adopted_pages": 2}
    # replays are cheap no-ops (trie dedup), never double-alloc
    again = fleet.migrate_chain(int(fps[-1]), r0.name, r1.name)
    assert again == {"matched_pages": 2, "adopted_pages": 0}
    # an unknown fingerprint exports nothing
    assert fleet.migrate_chain(123456789, r0.name, r1.name) is None
    cont = Request(header + [60, 61], 6)
    assert r1.inject(cont)
    out = RequestHandle(cont).result(timeout=180)
    np.testing.assert_array_equal(
        out, ref_engine.generate(header + [60, 61], 6))
    after = (r1.snapshot_dict() or {}).get("counters", {})
    assert after.get("prefix_hits", 0) > before.get("prefix_hits", 0)


def test_sigkill_mid_stream_zero_drops_exactly_once(fleet, ref_engine):
    """THE crash contract, end to end: SIGKILL a worker while it is
    streaming; the launcher detects the death, hands every unfinished
    request back, and the router re-dispatches to the survivor —
    every handle completes, bitwise-equal to the single-engine run
    (exactly-once emission: the re-decoded prefix is deduped, so no
    token is ever delivered twice), with zero drops and a clean
    survivor sentinel. Runs LAST in file order: it consumes the
    module fleet."""
    rng = np.random.RandomState(3)
    specs = [(rng.randint(1, 256,
                          (int(rng.randint(2, 12)),)).tolist(), 12)
             for _ in range(10)]
    # warm the full program inventory in every worker first, so the
    # armed sentinels below prove the kill scenario compiles NOTHING
    # new on the survivor
    for rep in fleet.replicas():
        rep._rpc("warm_programs", timeout=180.0)
    fleet.arm_sentinels()
    handles = [fleet.submit(p, m) for p, m in specs]
    time.sleep(0.3)                     # let streams start
    victim = fleet.replicas()[0]
    survivor = fleet.replicas()[1]
    fleet.kill_hard(victim.name, timeout=60)
    outs = [h.result(timeout=180) for h in handles]
    for (p, m), out, h in zip(specs, outs, handles):
        assert h.status == "completed"
        np.testing.assert_array_equal(out, ref_engine.generate(p, m))
    snap = fleet.snapshot()
    assert snap["fleet"]["crashes"] == 1
    assert snap["router"]["redispatch_failed"] == 0
    assert victim.state == "gone"
    assert all(r.name != victim.name
               for r in fleet.router.replicas())
    s = survivor.sentinel_report()
    assert s is None or s.get("clean", True)
    # duplicate-emission pin: every completed handle has EXACTLY its
    # stream's tokens (a double delivery would show as length drift)
    for (p, m), out in zip(specs, outs):
        assert len(out) <= m
