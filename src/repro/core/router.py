"""RouterEngine — the replicated serving tier above the worker seam.

WebLLM isolates the engine behind the ServiceWorkerMLCEngine message
port precisely so a frontend can outlive, multiplex, and supervise
engine instances (§2.2).  This module is the layer that cashes that
in: a :class:`RouterEngine` owns a pool of N replicas — each a full
``MLCEngine`` behind its own ``ServiceWorkerMLCEngine`` port — and
exposes the SAME frontend API (``chat_completions_create`` / ``abort``
/ ``stats``), so callers scale out without changing a line.

Placement: prefix-affine dispatch
---------------------------------
The dominant workload is multi-round chat, and each replica's radix
:class:`~repro.core.prefix_cache.PrefixCache` is PER-REPLICA state: turn
2 of a conversation only reuses turn 1's KV pages if it lands on the
replica that served turn 1.  The router therefore keeps a lightweight
affinity map from page-granular token-prefix chains to replica slots,
built with the exact same keys the radix tree uses
(:func:`~repro.core.prefix_cache.page_prefix_keys` over the
chat-template-rendered, tokenized prompt).  Dispatch looks up the
longest mapped chain:

* **hit** — the mapped replica is healthy and not overloaded
  (``in_flight <= least_loaded + imbalance_limit``): route sticky, count
  an affinity hit;
* **miss / overloaded** — route least-loaded (ties broken by lifetime
  dispatch count, then slot), and write THIS conversation's chain to the
  map so its next turn is sticky.

Entries are ``(slot, generation)`` pairs in a bounded LRU; a replica
restart bumps its generation, so every affinity entry pointing at the
dead incarnation is invalidated in O(1) without scanning the map.

Supervision: health, draining, restart-on-crash
-----------------------------------------------
A monitor thread heartbeats every replica with a short-timeout
``stats()`` round-trip (doubling as the per-replica stats snapshot the
router aggregates).  A replica is declared dead when the heartbeat
times out, the port signals a crash, or a request surfaces a typed
:class:`~repro.core.worker.WorkerCrashed` /
:class:`~repro.core.engine.EngineCrashed`.  Death is handled, never
waited out: pending calls on that replica are failed immediately via
``kill_pending`` (clean typed error — no ``STALL_TIMEOUT_S`` hangs), the
slot's affinity entries are invalidated by the generation bump, and the
monitor respawns a fresh engine into the slot (``restarts`` counter).
``drain(slot)`` is the graceful variant: dispatch stops, in-flight
requests finish, then the replica is recycled (``recycles`` counter).

The router reaches into its OWN backends only for supervisor-level
setup (tokenizer + page size for affinity keys); the request path
crosses the JSON port like any other frontend caller.

Placement: replica ``i`` is built, and keeps stepping, on
``jax.local_devices()[i]`` (wrapping round when replicas outnumber
devices), so a four-chip host serves four replicas on four chips.
"""
from __future__ import annotations

import logging
import threading
import time
import uuid
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax

from repro.core import api
from repro.core.engine import EngineCrashed, MLCEngine
from repro.core.prefix_cache import page_prefix_keys
from repro.core.worker import ServiceWorkerMLCEngine, WorkerCrashed

_log = logging.getLogger("repro.router")


class NoHealthyReplicas(RuntimeError):
    """Every replica in the pool is dead or draining."""


class _Replica:
    """One persistent pool slot.  The engine/front objects inside it are
    replaced on restart; the slot record (and its lifetime counters)
    survives, and ``generation`` counts the incarnations — affinity
    entries and in-flight bookkeeping are validated against it."""

    def __init__(self, slot: int, backend: MLCEngine,
                 front: ServiceWorkerMLCEngine):
        self.slot = slot
        self.replica_id = f"r{slot}"
        self.backend = backend
        self.front = front
        self.generation = 0
        self.state = "healthy"            # healthy | draining | dead
        self.respawning = False
        self.in_flight = 0                # current incarnation only
        self.dispatches = 0               # lifetime
        self.served = 0                   # lifetime, completed cleanly
        self.affinity_hits = 0            # lifetime
        self.restarts = 0                 # crash respawns
        self.recycles = 0                 # drain respawns
        self.spawn_failures = 0           # factory raised during respawn
        self.last_stats: Optional[dict] = None   # heartbeat snapshot


class RouterEngine:
    """A pool of ServiceWorkerMLCEngine replicas behind one frontend API.

    ``engine_factory`` must return a fully loaded :class:`MLCEngine`
    (same models in every replica) — it is called once per slot at
    construction and again whenever a dead or drained replica is
    respawned.
    """

    # every access outside ``with self._lock`` is a lint finding
    # (repro.analysis pass 1); ``_GUARDED_FIELDS`` covers the mutable
    # ``_Replica`` record fields, which share the router's lock
    _GUARDED_BY = {
        "_lock": ("_replicas", "_affinity", "_rids", "_completion_tokens",
                  "_t0", "_monitor_crashed"),
    }
    _GUARDED_FIELDS = {
        "_lock": ("state", "generation", "in_flight", "dispatches",
                  "served", "affinity_hits", "restarts", "recycles",
                  "respawning", "spawn_failures", "last_stats", "front",
                  "backend"),
    }

    def __init__(self, engine_factory: Callable[[], MLCEngine],
                 replicas: int = 2, *,
                 heartbeat_s: float = 0.5,
                 heartbeat_timeout_s: float = 10.0,
                 imbalance_limit: int = 4,
                 affinity_capacity: int = 8192):
        assert replicas >= 1
        self._factory = engine_factory
        self.heartbeat_s = heartbeat_s
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.imbalance_limit = imbalance_limit
        self.affinity_capacity = affinity_capacity
        self._lock = threading.Lock()
        #: replica ``slot`` lives on ``_devices[slot % len(_devices)]``:
        #: one chip per replica while there are chips to go round
        self._devices = jax.local_devices()
        self._replicas: List[_Replica] = []
        for slot in range(replicas):
            backend = self._spawn(slot)
            front = ServiceWorkerMLCEngine(backend, replica_id=f"r{slot}")
            self._replicas.append(_Replica(slot, backend, front))
        # affinity keys mirror each replica's PrefixCache: tokenizer +
        # page size per model, read once from replica 0 (the factory
        # loads identical models everywhere)
        self._models: Dict[str, Tuple[object, int]] = {}
        for name, lm in self._replicas[0].backend.models.items():
            r = lm.runner
            ps = (getattr(r, "page_size", None)
                  or getattr(getattr(r, "runner", None), "page_size", None)
                  or 16)
            self._models[name] = (lm.tokenizer, int(ps))
        #: hash-chain -> (slot, generation), LRU-bounded
        self._affinity: "OrderedDict[int, Tuple[int, int]]" = OrderedDict()
        self._rids: Dict[str, Tuple[_Replica, int]] = {}
        self._completion_tokens = 0
        self._t0: Optional[float] = None       # first dispatch
        self._monitor_crashed: Optional[str] = None
        self._stop = threading.Event()
        self._monitor_thread = threading.Thread(target=self._monitor,
                                                name="repro-router-monitor",
                                                daemon=True)
        self._monitor_thread.start()

    def _spawn(self, slot: int) -> MLCEngine:
        """Build replica ``slot``'s engine under ``jax.default_device`` of
        its own chip: the factory's params and KV pools are allocated
        there, and the engine keeps stepping there (it records the
        device it was built under)."""
        with jax.default_device(self._devices[slot % len(self._devices)]):
            return self._factory()

    def replica_engines(self) -> List[MLCEngine]:
        """The replicas' current engines, by slot (for placement checks
        and tests; requests go through the frontend API)."""
        with self._lock:
            return [r.backend for r in self._replicas]

    # -- placement -------------------------------------------------------
    def _prompt_keys(self, req: api.ChatCompletionRequest) -> List[tuple]:
        """Page-granular prefix keys for a request — the SAME rendering
        + tokenization + paging the target engine will perform, so the
        affinity map and the replica's radix tree agree on what 'same
        prefix' means."""
        ent = self._models.get(req.model)
        if ent is None:
            return []                     # unknown model: plain balancing
        tok, ps = ent
        try:
            prompt = tok.apply_chat_template(
                [m.__dict__ for m in req.messages])
            ids = tok.encode(prompt)
        except Exception:
            return []
        return page_prefix_keys(ids, ps)

    def _dispatch(
            self, model: str, keys: List[tuple], rid: str,
    ) -> Tuple[_Replica, int, ServiceWorkerMLCEngine, bool]:
        """Pick a replica (affinity-sticky with least-loaded fallback),
        record the request and the conversation's chain.  Returns
        ``(replica, generation, front, was_affinity_hit)`` — generation
        and front are captured under the lock so the caller never reads
        mutable replica fields unlocked."""
        chain: List[int] = []
        h = hash(("affinity", model))
        with self._lock:
            healthy = [r for r in self._replicas if r.state == "healthy"]
            if not healthy:
                raise NoHealthyReplicas(
                    "no healthy replicas (all dead or draining)")
            best = None
            for key in keys:
                h = hash((h, key))
                chain.append(h)
                ent = self._affinity.get(h)
                if ent is not None:
                    best = ent            # deepest mapped chain wins
            cand = None
            if best is not None:
                slot, gen = best
                r = self._replicas[slot]
                # generation check = O(1) invalidation of entries that
                # point at a crashed incarnation
                if r.generation == gen and r.state == "healthy":
                    cand = r
            least = min(healthy, key=lambda r: (r.in_flight, r.dispatches,
                                                r.slot))
            # stickiness vs imbalance: follow the prefix unless the
            # sticky replica is way more loaded than the emptiest one
            if (cand is not None
                    and cand.in_flight
                    <= least.in_flight + self.imbalance_limit):
                chosen, hit = cand, True
            else:
                chosen, hit = least, False
            chosen.in_flight += 1
            chosen.dispatches += 1
            if hit:
                chosen.affinity_hits += 1
            ent = (chosen.slot, chosen.generation)
            for ch in chain:              # every depth -> longest match
                self._affinity[ch] = ent
                self._affinity.move_to_end(ch)
            while len(self._affinity) > self.affinity_capacity:
                self._affinity.popitem(last=False)
            self._rids[rid] = (chosen, chosen.generation)
            if self._t0 is None:
                self._t0 = time.time()
            gen, front = chosen.generation, chosen.front
        return chosen, gen, front, hit

    def _finish(self, rid: str, served: bool):
        with self._lock:
            ent = self._rids.pop(rid, None)
            if ent is None:
                return
            rep, gen = ent
            if rep.generation == gen:     # not restarted underneath us
                if rep.in_flight > 0:
                    rep.in_flight -= 1
                if served:
                    rep.served += 1

    def _count_usage(self, usage):
        if usage is None:
            return
        with self._lock:
            self._completion_tokens += int(usage.completion_tokens or 0)

    # -- frontend API ----------------------------------------------------
    def chat_completions_create(
            self, request: Union[api.ChatCompletionRequest, dict],
            request_id: Optional[str] = None):
        """Same contract as ``ServiceWorkerMLCEngine``: a response for
        blocking calls, a chunk iterator for ``stream=True``; pass a
        ``request_id`` to make the call abortable from another thread.
        A replica dying mid-request raises a typed ``WorkerCrashed`` /
        ``EngineCrashed`` promptly; the replica is respawned behind the
        scenes and later requests re-route."""
        req = (api.ChatCompletionRequest.from_dict(request)
               if isinstance(request, dict) else request)
        rid = request_id or uuid.uuid4().hex
        rep, gen, front, _hit = self._dispatch(
            req.model, self._prompt_keys(req), rid)
        try:
            out = front.chat_completions_create(req, request_id=rid)
        except BaseException as e:
            self._finish(rid, served=False)
            if isinstance(e, (WorkerCrashed, EngineCrashed)):
                self._handle_crash(rep, gen, str(e))
            raise
        if req.stream:
            return self._wrap_stream(rep, gen, rid, out)
        self._finish(rid, served=True)
        self._count_usage(out.usage)
        return out

    def _wrap_stream(self, rep: _Replica, gen: int, rid: str, it):
        ok = False
        try:
            for chunk in it:
                if chunk.usage is not None:
                    self._count_usage(chunk.usage)
                yield chunk
            ok = True
        except (WorkerCrashed, EngineCrashed) as e:
            self._handle_crash(rep, gen, str(e))
            raise
        finally:
            # closing THIS iterator mid-stream must close the worker
            # iterator NOW (which posts the abort that frees backend
            # slots/pages) — not whenever the GC finalizes it
            it.close()
            self._finish(rid, served=ok)

    def abort(self, request_id: str):
        """Cancel an in-flight request wherever it was routed."""
        with self._lock:
            ent = self._rids.get(request_id)
            front = ent[0].front if ent is not None else None
        if front is not None:
            front.abort(request_id)

    def stats(self, model: Optional[str] = None) -> dict:
        """Router-level observability: per-replica
        in-flight/served/affinity-hit-rate/restarts plus aggregate
        completion-token throughput.  ``engine`` per replica is the
        latest heartbeat stats snapshot (None until the first beat).
        ``model`` filters that snapshot like ``MLCEngine.stats``."""
        with self._lock:
            dispatches = sum(r.dispatches for r in self._replicas)
            hits = sum(r.affinity_hits for r in self._replicas)
            elapsed = (time.time() - self._t0) if self._t0 else 0.0
            per = []
            for r in self._replicas:
                eng = r.last_stats
                if model is not None and isinstance(eng, dict):
                    eng = eng.get(model)
                per.append({
                    "replica": r.replica_id, "state": r.state,
                    "generation": r.generation,
                    "in_flight": r.in_flight, "dispatches": r.dispatches,
                    "served": r.served, "affinity_hits": r.affinity_hits,
                    "affinity_hit_rate": (r.affinity_hits / r.dispatches
                                          if r.dispatches else 0.0),
                    "restarts": r.restarts, "recycles": r.recycles,
                    "spawn_failures": r.spawn_failures,
                    "engine": eng,
                })
            return {
                "replicas": len(self._replicas),
                "monitor_crashed": self._monitor_crashed,
                "dispatches": dispatches,
                "affinity_hits": hits,
                "affinity_hit_rate": (hits / dispatches
                                      if dispatches else 0.0),
                "affinity_entries": len(self._affinity),
                "restarts": sum(r.restarts for r in self._replicas),
                "recycles": sum(r.recycles for r in self._replicas),
                "aggregate_completion_tokens": self._completion_tokens,
                "aggregate_tok_s": (self._completion_tokens / elapsed
                                    if elapsed > 0 else 0.0),
                "per_replica": per,
            }

    # -- supervision -----------------------------------------------------
    def drain(self, slot: int):
        """Graceful: stop dispatching to ``slot``, let in-flight
        requests finish, then recycle it (fresh engine, ``recycles`` +=
        1).  No-op unless the replica is currently healthy."""
        with self._lock:
            rep = self._replicas[slot]
            if rep.state == "healthy":
                rep.state = "draining"

    def _handle_crash(self, rep: _Replica, gen: int, reason: str):
        """Declare one incarnation dead (idempotent): fail its pending
        calls with a typed error NOW; the monitor respawns it."""
        with self._lock:
            if rep.generation != gen or rep.state == "dead":
                return
            rep.state = "dead"
            front = rep.front
        front.kill_pending(
            f"replica {rep.replica_id} crashed: {reason}")

    def _respawn(self, rep: _Replica, counter: str):
        try:
            backend = self._spawn(rep.slot)
            front = ServiceWorkerMLCEngine(backend,
                                           replica_id=rep.replica_id)
        except Exception as e:
            _log.warning("respawn of %s failed: %r", rep.replica_id, e)
            with self._lock:              # stay dead; monitor retries
                rep.spawn_failures += 1
                rep.respawning = False
            return
        with self._lock:
            rep.backend = backend
            rep.front = front
            rep.generation += 1           # invalidates old affinity
            rep.in_flight = 0
            rep.last_stats = None
            setattr(rep, counter, getattr(rep, counter) + 1)
            rep.state = "healthy"
            rep.respawning = False

    def _monitor(self):
        """Supervision loop: one :meth:`_beat` per replica per period.
        A crash of the monitor itself is recorded (``monitor_crashed``
        in :meth:`stats`) instead of silently ending supervision."""
        try:
            while not self._stop.wait(self.heartbeat_s):
                with self._lock:
                    reps = list(self._replicas)
                for rep in reps:
                    self._beat(rep)
        except BaseException as e:
            _log.error("router monitor thread crashed: %r", e)
            with self._lock:
                self._monitor_crashed = repr(e)

    def _beat(self, rep: _Replica):
        """One heartbeat for one replica: respawn it if dead, complete a
        drain, else probe with a short-timeout ``stats()`` round-trip
        (the liveness check AND the aggregated stats snapshot).  Split
        out from :meth:`_monitor` so tests can intercept it."""
        with self._lock:
            state, gen, front = rep.state, rep.generation, rep.front
            spawn = state == "dead" and not rep.respawning
            if spawn:
                rep.respawning = True
        if spawn:
            threading.Thread(
                target=self._respawn, args=(rep, "restarts"),
                name=f"repro-router-respawn[{rep.replica_id}]",
                daemon=True).start()
            return
        if state == "dead":
            return
        if state == "draining":
            with self._lock:
                done = rep.in_flight == 0 and rep.state == "draining"
                if done:
                    rep.state = "dead"
                    rep.respawning = True
            if done:
                try:                      # graceful: nothing in flight
                    front.shutdown()
                except Exception as e:
                    _log.warning("drain shutdown of %s failed: %r",
                                 rep.replica_id, e)
                threading.Thread(
                    target=self._respawn, args=(rep, "recycles"),
                    name=f"repro-router-respawn[{rep.replica_id}]",
                    daemon=True).start()
            return
        try:
            snap = front.stats(timeout=self.heartbeat_timeout_s)
            with self._lock:
                if rep.generation == gen:  # not restarted underneath us
                    rep.last_stats = snap
        except (TimeoutError, WorkerCrashed) as e:
            self._handle_crash(rep, gen, f"heartbeat failed: {e}")
        except Exception as e:
            # an error REPLY means the worker is alive — note it, move on
            _log.info("heartbeat reply error from %s: %r",
                      rep.replica_id, e)

    def shutdown(self):
        """Stop the monitor and shut every replica down."""
        self._stop.set()
        with self._lock:
            fronts = [(r.replica_id, r.front) for r in self._replicas]
        for replica_id, front in fronts:
            try:
                front.shutdown()
            except Exception as e:
                _log.info("shutdown of %s: %r", replica_id, e)
