"""Wire schema for the multi-process fleet (fleet/proc/).

Everything that crosses the process boundary is defined HERE, as plain
picklable data, so the protocol is auditable in one file:

Worker spec (pickled once, at spawn)
    :class:`WorkerSpec` — enough to rebuild the model + engine inside a
    fresh process: config kwargs (dtype as a STRING — jnp dtypes do not
    pickle portably), a params seed (every worker re-derives identical
    weights from ``PRNGKey(params_seed)``, which is what makes
    re-dispatch after a crash bitwise-safe), engine kwargs, the JAX
    platform the worker runs on (stated, never defaulted; pinned in
    the worker's environment before JAX initializes).

Command frames (parent -> worker, on the command queue)
    ``("rpc", seq, op, payload)``   request/reply; the worker answers
                                    with a ``reply`` frame echoing seq.
    ``("cast", op, payload)``       one-way (e.g. ``cancel`` — best
                                    effort, no reply to wait on).
    ``("stop",)``                   exit the worker loop (after a
                                    shutdown rpc already closed the
                                    engine).

Event frames (worker -> parent, on the event queue)
    ``("ready", info)``             engine built; info carries
                                    ``page_size``/``max_batch``/``pid``.
    ``("reply", seq, ok, payload)`` rpc answer; payload is the result
                                    or, when not ok, an error string.
    ``("tok", rid, fseq, tok)``     ONE generated token for request
                                    ``rid``; ``fseq`` counts 0,1,2,...
                                    per rid — the transport enforces
                                    the monotone order, and re-dispatch
                                    dedup drops ``fseq < skip``.
    ``("done", rid, fseq, state, err)``  terminal frame; fseq equals
                                    the number of tok frames emitted.
    ``("evt", kind, payload)``      out-of-band worker event (no seq,
                                    no ordering contract): the engine's
                                    chain-completion hook surfaces as
                                    ``kind="chain_complete"`` with
                                    ``payload={"rid", "fp", "fps",
                                    "pages", "prompt_tokens"}`` — what
                                    the fleet's migration policy rides
                                    (router-driven prefill→decode
                                    handoff). Delivered to the
                                    transport's ``on_event`` callback;
                                    unknown kinds are dropped.
    ``("fatal", traceback_text)``   worker crashed outside an rpc.

Request serialization
    The PARENT-side :class:`~paddle_tpu.serving.scheduler.Request` is
    authoritative: it owns the caller's stream/done machinery and its
    handle must keep working across the hop (and across re-dispatch to
    a different worker). Only the request's *parameters* travel —
    :func:`request_to_wire` — and the worker builds a local twin whose
    tokens are relayed back as ``tok`` frames keyed by the PARENT's
    request id. Deadlines travel as REMAINING seconds because
    ``time.monotonic()`` values are not comparable across processes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

__all__ = ["WorkerSpec", "request_to_wire", "request_from_wire"]


@dataclass
class WorkerSpec:
    """Everything a spawned worker needs to build its engine.

    ``platform`` is the JAX platform the worker runs on, stated by the
    caller (no default: a fleet pinned to the CPU by omission looks
    like a chip run). A chip belongs to one process, so ``"tpu"``
    workers need a parent that stays off JAX and a host with a chip
    per worker; the worker refuses to serve on any other platform than
    the one named here.
    ``cfg_kw`` are ``LlamaConfig`` kwargs with ``dtype`` as a string
    (``"float32"``); ``engine_kw`` are ``ServingEngine`` kwargs.
    ``params_seed`` feeds ``jax.random.PRNGKey`` — every worker in a
    fleet must use the SAME seed so a re-dispatched request decodes
    the same stream on any replica (greedy/fixed-seed sampling is
    deterministic given identical weights).
    """
    platform: str
    cfg_kw: dict = field(default_factory=dict)
    params_seed: int = 0
    engine_kw: dict = field(default_factory=dict)
    warm: bool = False

    def process_env(self) -> dict:
        """What the worker's environment must hold before JAX starts."""
        return {"JAX_PLATFORMS": self.platform}


def request_to_wire(req) -> dict:
    """Serialize a Request's parameters (NOT its caller machinery) for
    the hop; ``rid`` is the parent-side id every later frame keys on."""
    remaining: Optional[float] = None
    if req.deadline_s is not None:
        remaining = req.deadline_s - time.monotonic()
    return {"rid": int(req.id),
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "deadline": remaining,
            "temperature": float(req.temperature),
            "top_p": float(req.top_p),
            "top_k": int(req.top_k),
            "seed": int(req.seed)}


def request_from_wire(d: dict):
    """Build the worker-local twin (imports deferred: this module must
    stay import-light — the spawn child imports it before JAX env is
    final)."""
    from ...scheduler import Request
    timeout = d.get("deadline")
    req = Request(d["prompt"], d["max_new_tokens"],
                  eos_token_id=d.get("eos_token_id"),
                  temperature=d.get("temperature", 0.0),
                  top_p=d.get("top_p", 1.0),
                  top_k=d.get("top_k", 0),
                  seed=d.get("seed", 0))
    if timeout is not None:
        req.deadline_s = time.monotonic() + max(0.0, float(timeout))
    return req
