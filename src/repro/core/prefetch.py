"""Async expert prefetch + opportunistic residency (ROADMAP item 1).

The paper's headline mechanism is expert loading running *in parallel*
with expert computation.  ``DecodeClock`` co-simulates that overlap;
this module makes wall-clock decode actually do it, without ever
touching the load-bearing invariant (tokens bit-identical to
``greedy_generate(..., transport=policy)``).

The design splits every load into a *fetch* and a *commit*:

  * the **fetch** — ``ExpertStore.unpack_shard`` — is a pure function of
    ``(layer, expert)``: ship the packed shard, dequantize on arrival.
    It is worker-agnostic and side-effect-free, so it may run on any
    thread, in any order, at any time between prediction and use.
  * the **commit** — worker assignment, slot insertion, the
    ``LoadEvent`` log and the ``bytes_moved`` accounting — happens on
    the main thread at the exact program points the synchronous engine
    uses (predicted loads before the layer's waves, reloads inside
    them).  The commit consumes a prefetched payload when one is ready
    and falls back to an inline fetch when it is not.

Because scheduling state only ever changes at commit points, the event
log, byte accounting and token stream are *bit-identical under every
completion order* — an executor can only move WHEN bytes are fetched,
never what computes or what is recorded.  ``ChaosExecutor`` weaponizes
that contract: a seeded adversarial schedule (permuted completions,
early runs, dropped transfers) that the chaos suite drives through
hundreds of seeds.

``PrefetchExecutor`` is the SEP-peek-driven load queue: the engine
enqueues predicted experts for every MoE layer within the peek horizon
as soon as predictions exist (for the SEP shadow: the first layers at
token start, then one layer further at each layer served), and joins
per-layer at the wave boundary.  An engine that loads physically and is
given no executor fetches on ``loader_pool()``, the process's one pool
of loader threads, so the copies run while the decode thread computes.

Opportunistic residency (``LRUResidency`` / ``GateStatsResidency``)
rides on ``WorkerSlots.release``: after a layer computes, its workers'
residents are *released* (free-slot residents) instead of evicted.  A
later predicted load or reload that finds its expert still resident
re-hits — no load event, zero bytes moved — and only displacement
pressure (a full worker needing the slot) actually evicts, with the
policy choosing the victim among released residents.  Residency may
only remove *loads*; compute still consumes physically resident slot
contents, so tokens cannot change.
"""
from __future__ import annotations

import functools
import random
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

from .predictor import layers_within_horizon
from .spans import span

Key = Tuple[int, int, int]           # (step, layer, expert)

LOADER_THREADS = 4
_loader_pool: Optional[ThreadPoolExecutor] = None
_loader_lock = threading.Lock()


def loader_pool() -> ThreadPoolExecutor:
    """The process's one pool of loader threads, made on first use and
    shared by every ``ThreadedExecutor`` (a test session builds
    hundreds of engines; they add no threads)."""
    global _loader_pool
    with _loader_lock:
        if _loader_pool is None:
            _loader_pool = ThreadPoolExecutor(
                max_workers=LOADER_THREADS, thread_name_prefix="loader")
        return _loader_pool


# ------------------------------------------------------------ executors
class SyncExecutor:
    """Degenerate executor: remembers submitted fetch thunks and runs
    them inline at collect time.  The async plumbing with zero
    concurrency — the bit-exactness baseline every other executor is
    compared against."""

    kind = "sync"

    def __init__(self) -> None:
        self._pending: "OrderedDict[Key, Callable[[], object]]" = \
            OrderedDict()

    def submit(self, key: Key, fn: Callable[[], object]) -> None:
        self._pending.setdefault(key, fn)

    def collect(self, keys: Sequence[Key]) -> Dict[Key, object]:
        out = {}
        for k in keys:
            fn = self._pending.pop(k, None)
            if fn is not None:
                out[k] = fn()
        return out

    def discard(self, keys: Sequence[Key]) -> int:
        n = 0
        for k in keys:
            if self._pending.pop(k, None) is not None:
                n += 1
        return n

    def ready(self, keys: Sequence[Key]) -> int:
        return 0                     # nothing runs before its collect

    def close(self) -> None:
        self._pending.clear()


class ThreadedExecutor:
    """Real background fetches on ``loader_pool()``.  ``collect`` joins
    the demanded futures (the wave boundary); everything else keeps
    transferring while the main thread runs grouped-FFN compute.  The
    pool is the process's: ``close`` drops only this executor's
    fetches."""

    kind = "thread"

    def __init__(self) -> None:
        self._pool = loader_pool()
        self._futs: Dict[Key, object] = {}

    def submit(self, key: Key, fn: Callable[[], object]) -> None:
        if key not in self._futs:
            self._futs[key] = self._pool.submit(fn)

    def collect(self, keys: Sequence[Key]) -> Dict[Key, object]:
        out = {}
        for k in keys:
            fut = self._futs.pop(k, None)
            if fut is not None:
                out[k] = fut.result()
        return out

    def discard(self, keys: Sequence[Key]) -> int:
        n = 0
        for k in keys:
            fut = self._futs.pop(k, None)
            if fut is not None:
                fut.cancel()
                n += 1
        return n

    def ready(self, keys: Sequence[Key]) -> int:
        return sum(1 for k in keys
                   if k in self._futs and self._futs[k].done())

    def close(self) -> None:
        self.discard(list(self._futs))


class ChaosExecutor:
    """Deterministic adversarial executor for the chaos suite.

    Holds submitted fetches and, at every ``collect``, replays a seeded
    adversarial schedule: completion order is a fresh permutation of
    everything pending, non-demanded tasks may complete *early* (run
    ahead of their wave), and demanded tasks may be *dropped* — the
    transfer failed or timed out, forcing the caller onto the inline
    fallback path.  Deferred tasks model injected transfer delays: they
    simply stay pending until a later collect (or are discarded as
    stale at token end).

    Everything is driven by one ``random.Random(seed)``: the same seed
    against the same call sequence replays the identical schedule, so a
    failing chaos case reproduces exactly from its printed seed.  The
    schedule is also journaled in ``self.log`` for debugging.
    """

    kind = "chaos"

    def __init__(self, seed: int, p_run_ahead: float = 0.5,
                 p_drop: float = 0.15, p_defer: float = 0.25) -> None:
        self.seed = seed
        self.rng = random.Random(seed)
        self.p_run_ahead = p_run_ahead
        self.p_drop = p_drop
        self.p_defer = p_defer
        self._pending: "OrderedDict[Key, Callable[[], object]]" = \
            OrderedDict()
        self._done: Dict[Key, object] = {}
        self.log: List[Tuple[str, Key]] = []

    def submit(self, key: Key, fn: Callable[[], object]) -> None:
        if key not in self._pending and key not in self._done:
            self._pending[key] = fn
            self.log.append(("submit", key))

    def collect(self, keys: Sequence[Key]) -> Dict[Key, object]:
        demanded = set(keys)
        order = list(self._pending)
        self.rng.shuffle(order)                     # permuted completions
        out: Dict[Key, object] = {}
        for k in order:
            if k in demanded:
                r = self.rng.random()
                if r < self.p_drop:                 # failed transfer
                    self._pending.pop(k)
                    self.log.append(("drop", k))
                elif r < self.p_drop + self.p_defer:
                    # delayed past the deadline: also an inline fallback,
                    # but the task stays in flight (completes late)
                    self.log.append(("defer", k))
                else:
                    out[k] = self._pending.pop(k)()
                    self.log.append(("run", k))
            elif self.rng.random() < self.p_run_ahead:
                self._done[k] = self._pending.pop(k)()   # early completion
                self.log.append(("early", k))
        for k in keys:                              # completed-early wins
            if k not in out and k in self._done:
                out[k] = self._done.pop(k)
                self.log.append(("join-early", k))
        return out

    def discard(self, keys: Sequence[Key]) -> int:
        n = 0
        for k in keys:
            if (self._pending.pop(k, None) is not None
                    or self._done.pop(k, None) is not None):
                self.log.append(("discard", k))
                n += 1
        return n

    def ready(self, keys: Sequence[Key]) -> int:
        return sum(1 for k in keys if k in self._done)

    def close(self) -> None:
        self._pending.clear()
        self._done.clear()


def make_executor(spec, ahead: bool = False):
    """``None`` | ``'sync'`` | ``'thread'`` | an executor instance.
    ``None`` fetches ahead on the loader pool where ``ahead`` (an engine
    that loads physically on the grouped path) and keeps no executor
    elsewhere; ``'thread'`` asks for the loader pool outright."""
    if spec is None:
        return ThreadedExecutor() if ahead else None
    if isinstance(spec, str):
        if spec == "sync":
            return SyncExecutor()
        if spec == "thread":
            return ThreadedExecutor()
        raise ValueError(f"unknown prefetch executor {spec!r}")
    if not all(hasattr(spec, m) for m in ("submit", "collect", "ready")):
        raise TypeError("prefetch executor needs submit()/collect()/"
                        "ready()")
    return spec


# ----------------------------------------------------------- load queue
class PrefetchExecutor:
    """The SEP-peek-driven load queue.

    ``enqueue`` walks the pending predictions within the peek horizon
    and submits one worker-agnostic fetch per (step, layer, expert);
    ``collect`` joins a layer's demanded experts at its wave boundary
    and returns whatever payloads the executor produced (missing ones
    fall back to inline loads at commit); ``fetch_now`` fans a wave's
    reload set out through the executor so even misses transfer in
    parallel; ``finish_token`` retires stale tasks (predictions that
    never became loads — mispredicts and residency re-hits).

    Every fetch runs inside an ``odmoe.expert_load`` span on whatever
    thread runs it, with ``ahead=1`` when a prediction submitted it
    before its layer was served; each join is an ``odmoe.expert_wait``
    span on the thread that commits.
    """

    def __init__(self, store, executor, *, horizon: int = 0,
                 physical: bool = True, packed: bool = False) -> None:
        self.store = store
        self.executor = executor
        self.horizon = horizon
        self.physical = physical
        self.packed = packed     # fetch DeviceShards for packed-resident slots
        self._enqueued: set = set()
        self.stats = {"submitted": 0, "demand_fetches": 0, "prefetched": 0,
                      "inline": 0, "stale": 0}

    def _fetch_fn(self, layer: int, expert: int, predicted: bool,
                  ahead: bool):
        fetch = functools.partial(
            self.store.device_shard if self.packed
            else self.store.unpack_shard, layer, expert, self.physical)
        args = dict(layer=layer, expert=expert,
                    nbytes=self.store.packed_bytes(layer, expert),
                    predicted=int(predicted), ahead=int(ahead))

        def run():
            with span("expert_load", **args):
                return fetch()
        return run

    def enqueue(self, step: int, current_layer: int,
                pending: Mapping[int, object],
                select: Optional[Callable[[int, List[int]], List[int]]]
                = None) -> None:
        """Submit fetches for the predicted experts of every MoE layer
        within the horizon, from ``current_layer`` (the layer being
        served; -1 before the first).  ``select(layer, experts)`` keeps
        those worth fetching: the engine drops residents that will
        re-hit and the overflow its schedule cannot place."""
        for tgt in layers_within_horizon(list(pending), current_layer,
                                         self.horizon):
            experts = list(dict.fromkeys(
                int(x) for x in pending[tgt].reshape(-1)))
            if select is not None:
                experts = select(tgt, experts)
            for e in experts:
                key = (step, tgt, e)
                if key in self._enqueued:
                    continue
                self._enqueued.add(key)
                self.stats["submitted"] += 1
                self.executor.submit(key, self._fetch_fn(
                    tgt, e, True, tgt > current_layer))

    def _join(self, step: int, layer: int,
              experts: Sequence[int]) -> Dict[int, object]:
        queued = [k for k in ((step, layer, int(e)) for e in experts)
                  if k in self._enqueued]
        if not queued:
            return {}
        with span("expert_wait", experts=len(queued),
                  ready=self.executor.ready(queued)):
            got = self.executor.collect(queued)
        self._enqueued.difference_update(queued)
        return {k[2]: v for k, v in got.items()}

    def collect(self, step: int, layer: int,
                experts: Sequence[int]) -> Dict[int, object]:
        """Join the layer's demanded experts at its wave boundary.
        Returns ``{expert: payload}`` for fetches that completed; a
        demanded expert with no payload (never enqueued, dropped, or
        deferred by chaos) loads inline at commit."""
        got = self._join(step, layer, experts)
        self.stats["prefetched"] += len(got)
        self.stats["inline"] += len(experts) - len(got)
        return got

    def fetch_now(self, step: int, layer: int,
                  experts: Sequence[int]) -> Dict[int, object]:
        """Demand-fetch a wave's reload set through the executor: with a
        threaded executor the wave's misses transfer concurrently
        instead of one blocking ``unpack_shard`` at a time."""
        for e in experts:
            key = (step, layer, int(e))
            if key not in self._enqueued:
                self._enqueued.add(key)
                self.stats["demand_fetches"] += 1
            self.executor.submit(key, self._fetch_fn(layer, int(e),
                                                     False, False))
        return self._join(step, layer, experts)

    def finish_token(self, step: int) -> None:
        """Token boundary: retire fetches that never became loads."""
        stale = [k for k in self._enqueued if k[0] <= step]
        self.executor.discard(stale)
        for k in stale:
            self._enqueued.discard(k)
        self.stats["stale"] += len(stale)

    def close(self) -> None:
        self.executor.close()


# ---------------------------------------------------- residency policies
class ResidencyPolicy:
    """Victim selection among *released* (opportunistically resident)
    experts when a full worker needs a slot.  Keys are ``(layer,
    expert)``.  Policies must be deterministic: the chaos suite pins
    byte accounting bit-identical across schedules, which displacement
    choices feed into."""

    name = "base"

    def note(self, key: Tuple[int, int]) -> None:
        """The expert was loaded or re-hit (a use)."""

    def credit(self, key: Tuple[int, int], mass: float) -> None:
        """The gate routed real probability mass through the expert."""
        self.note(key)

    def victim(self, candidates: Sequence[Tuple[int, int]]) -> Tuple[int,
                                                                     int]:
        raise NotImplementedError

    def forget(self, key: Tuple[int, int]) -> None:
        """The expert was displaced or its worker failed."""


class LRUResidency(ResidencyPolicy):
    """Evict the least-recently-used released resident (FlashMoE's LRU
    baseline).  Recency is a logical clock bumped on every load/re-hit/
    gate-credit; never-seen keys (shouldn't happen) evict first."""

    name = "lru"

    def __init__(self) -> None:
        self._clock = 0
        self._last: Dict[Tuple[int, int], int] = {}

    def note(self, key) -> None:
        self._last[key] = self._clock
        self._clock += 1

    def victim(self, candidates):
        return min(candidates,
                   key=lambda k: (self._last.get(k, -1), k))

    def forget(self, key) -> None:
        self._last.pop(key, None)


class GateStatsResidency(ResidencyPolicy):
    """Evict the released resident with the least accumulated gate mass
    (FlashMoE's learned-popularity direction, using the router's own
    statistics).  Popularity persists across displacement — it is a
    property of the expert, not of the slot — with recency then key id
    breaking ties deterministically."""

    name = "gate"

    def __init__(self) -> None:
        self._clock = 0
        self._mass: Dict[Tuple[int, int], float] = {}
        self._last: Dict[Tuple[int, int], int] = {}

    def note(self, key) -> None:
        self._last[key] = self._clock
        self._clock += 1

    def credit(self, key, mass: float) -> None:
        self._mass[key] = self._mass.get(key, 0.0) + float(mass)
        self.note(key)

    def victim(self, candidates):
        return min(candidates,
                   key=lambda k: (self._mass.get(k, 0.0),
                                  self._last.get(k, -1), k))

    def forget(self, key) -> None:
        self._last.pop(key, None)          # popularity survives


def resolve_residency(spec) -> Optional[ResidencyPolicy]:
    """``None`` | ``'lru'`` | ``'gate'`` | a policy instance."""
    if spec is None:
        return None
    if isinstance(spec, ResidencyPolicy):
        return spec
    if spec == "lru":
        return LRUResidency()
    if spec == "gate":
        return GateStatsResidency()
    raise ValueError(f"unknown residency policy {spec!r}")
