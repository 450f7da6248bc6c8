"""ServingLoop — continuous batching driven by the OD-MoE engine.

Each outer iteration: (1) resume preempted requests and admit deferred
ones as KV pages free up, then admit every request whose arrival time
the virtual clock has passed, running real prefill on admission (the
first token falls out of prefill, so TTFT = admission wait + prefill;
prefill executables are cached per pow2 prompt-length bucket, see
``repro.models.api.prefill``); with ``prefill_chunk=N`` long prompts
instead admit as *prefilling* placeholders whose modeled prefill cost
is paid one N-token chunk per iteration — prefill interleaves with
decode waves on the clock instead of stalling the batch, and the one
real bucketed prefill runs at the final chunk (chunked cache-extension
is not bitwise on this backend, time-slicing the clock is);
(2) refresh the runnable requests' SEP *peeks* — every request lacking
one is aligned per-request, composed, and stepped as ONE batched shadow
dispatch (``_ensure_peeks``) that yields each request's next-token
prediction without committing any shadow, so waiting requests never
drift (with ``engine.speculate=k`` the composed shadow instead rolls
out ``k`` draft steps, caching per-request predictions, drafts and the
per-step shadow snapshots); (3) let the
``BatchComposer`` pick <= max_batch requests, preferring overlapping
predicted expert sets; (4) run one composed ``decode_batch`` (or, when
speculating, a ``decode_batch_spec`` verify wave over ``B*k`` rows)
through the engine — shared worker fleet, shared expert store, load
events tagged with the batch's request ids — and charge its duration
on the ``DecodeClock``; (5) split the batch back into per-request
states — under speculation each request independently commits its
accepted prefix (capped by its remaining budget) and rolls its shadow
back to the matching snapshot — and retire finished requests.

Correctness and time are deliberately co-simulated: admission depends on
the clock, the clock depends on the composed traces, and both share one
event stream, so TTFT/TPOT/throughput come out of the same run that
checks bit-exactness.

KV memory is a first-class budget when the loop carries a
``repro.serve.kvpool.KVPool``: requests decode out of pool pages via
per-request page tables instead of dense ``max_cache_len`` buffers.
Admission is budget-aware — a request whose prompt pages do not fit is
*deferred* (FIFO, its TTFT absorbs the memory wait) rather than
allowed to over-commit the node.  When a running request crosses a
page boundary and the free list is empty, a runnable victim — the
*youngest* by default, the most deadline slack under
``preempt="slack"`` — is preempted: its pages are swapped out to host
byte-exactly (``DecodeClock.charge_kv_swap`` prices the transfer), and
it resumes — oldest first, page-exact — once retirements free pages.
Every exhaustion frees at least one victim's pages and one window must
fit the pool by construction, so the growing batch member always
progresses and every admitted request completes; preemption is
scheduling, never arithmetic.

The bit-exactness invariant (tested in tests/test_serving.py): every
request's token stream is bit-identical to running it alone through
``greedy_generate``, whatever batches it rode in — and, under a pool,
however often it was preempted and resumed.  Under a mixed-precision
transport policy (``ODMoEEngine(transport=...)``) the same holds
against ``greedy_generate(..., transport=...)``: the loop passes the
engine's policy to the ``DecodeClock`` so composed-step durations
price expert loads by packed wire bytes, and every load event carries
its scheme and payload for per-request codec accounting.

Serving survives fleet faults (tests/test_fleet.py): when the engine
carries a ``repro.fleet.FaultInjector``, worker kills/throttles fire
inside each composed ``decode_batch``; the loop keeps serving on the
surviving workers, records per-step liveness in
``StepRecord.alive_workers``, and ``ServeResult.degraded_report()``
splits TPOT into healthy- vs degraded-fleet steps.
"""
from __future__ import annotations

import heapq
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import (AlignmentPolicy, DecodeClock, LayerRecord,
                        ODMoEEngine, RTX3090_EDGE, ServingTimings,
                        TokenRecord, Trace, concat_cache_lists,
                        concat_shadow_states, degraded_tpot_report,
                        slice_cache_list, slice_shadow_state,
                        simulate_prefill_odmoe, wave_preds)
from repro.core.predictor import recall_counts
from repro.core.spans import install_gc_span, span
from repro.core.timing import HardwareProfile
from .composer import BatchComposer
from .kvpool import KVPool, PoolExhausted
from .request import Request, RequestQueue, RequestState


def preemption_victim(runnable: List[RequestState], policy: str,
                      now: float) -> RequestState:
    """Pick the preemption victim among ``runnable`` states.

    ``youngest`` (the default, the pinned historical behavior): the
    highest ``admit_seq`` — newest admission loses its pages first.

    ``slack``: the request with the most deadline slack (see
    ``RequestState.deadline_slack``) is the one that can best afford to
    sit out a swap round-trip.  Requests with no TPOT SLO have infinite
    slack, so best-effort traffic is always victimized before any
    SLO-bearing request; ties (including the all-infinite no-SLO case)
    fall back to youngest-first, which makes ``slack`` on an untagged
    trace behave exactly like the default policy."""
    if policy == "slack":
        return max(runnable,
                   key=lambda s: (s.deadline_slack(now), s.admit_seq))
    return max(runnable, key=lambda s: s.admit_seq)


class _AdmissionQueue:
    """Deferred-admission buffer.  ``fifo`` keeps strict arrival order
    (deque: O(1) at both ends — the old ``list.pop(0)`` shifted the
    tail, quadratic over a big deferred backlog).  ``priority`` orders
    by descending tenant weight, FIFO within a weight class (heap on
    ``(-weight, arrival_s, rid)``), so an interactive arrival can jump
    a deferred batch backlog — weight-based jumping is bounded
    starvation: equal-weight requests still serve FIFO."""

    def __init__(self, policy: str = "fifo"):
        self.policy = policy
        self._fifo: deque = deque()
        self._heap: list = []

    def push(self, req: Request) -> None:
        if self.policy == "priority":
            heapq.heappush(self._heap,
                           (-req.weight, req.arrival_s, req.rid, req))
        else:
            self._fifo.append(req)

    def peek(self) -> Request:
        return self._heap[0][3] if self.policy == "priority" \
            else self._fifo[0]

    def pop(self) -> Request:
        if self.policy == "priority":
            return heapq.heappop(self._heap)[3]
        return self._fifo.popleft()

    def __len__(self) -> int:
        return len(self._heap) + len(self._fifo)


@dataclass
class StepRecord:
    """One composed decode iteration: who rode, what it cost."""
    step: int
    request_ids: List[int]
    record: TokenRecord
    start_s: float
    duration_s: float
    stall_s: float
    alive_workers: int = -1      # fleet liveness after this step's faults
    kv_pages_used: int = -1      # pool occupancy after this step (paged)
    # one-pass queue population snapshot after this step (pending/
    # active/runnable/preempted/prefilling/finished) — the per-step
    # state summary big traces are graded on
    queue_counts: Optional[Dict[str, int]] = None


@dataclass
class ServeResult:
    outputs: Dict[int, np.ndarray]       # rid -> generated tokens
    timings: ServingTimings
    trace: Trace                         # composed-step trace (loads etc.)
    steps: List[StepRecord] = field(default_factory=list)
    states: Dict[int, RequestState] = field(default_factory=dict)
    n_workers: int = 0
    kv_stats: Optional[Dict] = None      # pool counters + swap seconds
    prefetch_stats: Optional[Dict] = None  # engine.prefetch_report()
    #                                       when prefetch/residency ran
    # speculative decoding (engine speculate > 1): aggregate and
    # per-request draft acceptance — {"speculate", "waves", "committed",
    # "acceptance", "per_request": {rid: {...}}}.  None when serving
    # decoded one token per step.
    spec_stats: Optional[Dict] = None

    @property
    def mean_batch(self) -> float:
        if not self.steps:
            return 0.0
        return float(np.mean([len(s.request_ids) for s in self.steps]))

    def tenant_report(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant p50/p95/p99 TTFT+TPOT and SLO attainment — the
        multi-tenant serving scorecard (see
        ``ServingTimings.per_tenant_report``; every field finite and
        empty-safe)."""
        return self.timings.per_tenant_report()

    def degraded_report(self) -> Dict[str, float]:
        """Healthy- vs degraded-fleet TPOT over the composed steps.  An
        all-healthy run is a well-defined explicit case (see
        ``repro.core.timing.degraded_tpot_report``): ``healthy_only``
        is True, the empty degraded bucket reports 0.0 and
        ``degradation_x`` is 1.0 — never NaN."""
        return degraded_tpot_report(
            [s.duration_s for s in self.steps],
            [s.alive_workers if s.alive_workers >= 0 else self.n_workers
             for s in self.steps],
            self.n_workers)


class ServingLoop:
    def __init__(self, engine: ODMoEEngine, *, max_batch: int = 4,
                 composer: Optional[BatchComposer] = None,
                 profile: HardwareProfile = RTX3090_EDGE,
                 policy: AlignmentPolicy = AlignmentPolicy(1, 1),
                 max_seq_len: int = 0,
                 kv_pool: Optional[KVPool] = None,
                 prefill_chunk: int = 0,
                 preempt: str = "youngest",
                 admit: str = "fifo"):
        self.engine = engine
        self.kv_pool = kv_pool
        self.composer = composer or BatchComposer(max_batch,
                                                  kv_pool=kv_pool)
        if kv_pool is not None and self.composer.kv_pool is None:
            self.composer.kv_pool = kv_pool   # budget-aware composition
        self.profile = profile
        self.policy = policy
        self.max_seq_len = max_seq_len
        # speculative wave width rides on the engine (speculate=k);
        # the loop only orchestrates peek rollout + per-request commits
        self.speculate = getattr(engine, "speculate", 1)
        # prompts longer than ``prefill_chunk`` admit as time-sliced
        # chunks (0 disables): modeled prefill cost charges one chunk
        # per serving iteration so running requests' decode waves
        # interleave with it; the REAL bucketed prefill runs once at
        # the final chunk — chunking shapes time, never arithmetic
        self.prefill_chunk = max(0, int(prefill_chunk))
        # scheduling policies (both pure scheduling, never arithmetic):
        # ``preempt`` picks the page-exhaustion victim (youngest-first
        # default keeps the historical pins; "slack" preempts the
        # request with the most TPOT-deadline headroom), ``admit``
        # orders arrivals and the deferred backlog ("priority" admits
        # by descending tenant weight, FIFO within a weight)
        if preempt not in ("youngest", "slack"):
            raise ValueError(f"unknown preemption policy {preempt!r}")
        if admit not in ("fifo", "priority"):
            raise ValueError(f"unknown admission policy {admit!r}")
        self.preempt_policy = preempt
        self.admit_policy = admit

    # ------------------------------------------------------------- admit
    def _admit(self, req: Request, cache_len: int, clock: DecodeClock
               ) -> RequestState:
        """Prefill ``req`` on the main node (real compute + modeled
        time); its first token is emitted here.  Paged serving adopts
        the prefilled KV straight into pool pages (the caller verified
        they fit)."""
        with span("admit", rid=req.rid, prompt_len=len(req.prompt)):
            eng = self.engine
            arrival_wait_end = clock.now
            t_pre = simulate_prefill_odmoe(
                eng.cfg, self.profile, len(req.prompt),
                n_workers=eng.sched.n_workers)
            clock.charge_prefill(t_pre)
            batch = {"tokens": jnp.asarray(req.prompt)[None, :]}
            token, cache_list, pos = eng.prefill_request(
                batch, cache_len, kv_pool=self.kv_pool,
                rid=req.rid if self.kv_pool is not None else None)
            state = RequestState(request=req, token=token,
                                 cache_list=cache_list, pos=pos,
                                 admit_s=arrival_wait_end,
                                 first_token_s=clock.now)
            state.admit_seq = self._admit_seq
            self._admit_seq += 1
            state.generated.append(int(token[0]))
            if eng.shadow is not None:
                state.shadow_state = self._shadow_start(batch, cache_len)
            return state

    def _shadow_start(self, batch, cache_len: int) -> dict:
        """A request's first shadow state.  Where the alignment policy
        overwrites both the shadow's token and its caches at the first
        peek, a shadow prefill would be thrown away, so none runs: the
        peek starts from the main model's state."""
        if self.policy.align_token_at(1) and self.policy.align_kv_at(1):
            return {"caches": None, "pos": None, "token": None}
        return self.engine.shadow.prefill_state(batch, cache_len)

    def _pool_fits_prompt(self, req: Request) -> bool:
        pool = self.kv_pool
        return pool is None or pool.can_alloc(pool.pages_for(len(req.prompt)))

    def _is_chunked(self, req: Request) -> bool:
        return bool(self.prefill_chunk
                    and len(req.prompt) > self.prefill_chunk)

    def _admission_fits(self, req: Request) -> bool:
        # a chunked prompt holds no pages until its final chunk, so it
        # always admits; the page claim is deferred to finalize
        return self._is_chunked(req) or self._pool_fits_prompt(req)

    def _admit_or_retire(self, req: Request, cache_len: int,
                         clock: DecodeClock, queue: RequestQueue) -> None:
        if self._is_chunked(req):
            n, c = len(req.prompt), self.prefill_chunk
            chunks = [c] * (n // c) + ([n % c] if n % c else [])
            # time-slice the ONE full-prompt prefill cost across the
            # chunks (last slice takes the float remainder so the total
            # is exact): prefill cost is not additive in prompt length
            # — per-chunk ``simulate_prefill_odmoe(chunk)`` calls paid
            # the per-layer expert-load floor once PER CHUNK, so a
            # chunked admission's clock total drifted from the
            # unchunked cost of the same prompt.  Chunking must shape
            # *when* the cost lands, never *how much* it is.
            t_full = simulate_prefill_odmoe(
                self.engine.cfg, self.profile, n,
                n_workers=self.engine.sched.n_workers)
            costs = [t_full * ch / n for ch in chunks]
            costs[-1] = t_full - sum(costs[:-1])
            state = RequestState(request=req, token=None, cache_list=[],
                                 pos=None, admit_s=clock.now,
                                 prefilling=True, prefill_chunks=chunks,
                                 prefill_chunk_s=costs)
            state.admit_seq = self._admit_seq
            self._admit_seq += 1
            queue.activate(state)
            return
        state = self._admit(req, cache_len, clock)
        queue.activate(state)
        if state.done:                       # max_new_tokens == 1
            state.finish_s = clock.now
            self._retire(state, queue)

    # ------------------------------------------------ chunked prefill
    def _advance_prefills(self, queue: RequestQueue, clock: DecodeClock,
                          cache_len: int) -> bool:
        """Charge one prefill chunk per mid-prefill request (admission
        order), finalizing those whose last chunk just landed: the real
        bucketed prefill runs once over the WHOLE prompt — identical
        arithmetic to unchunked admission — while the modeled clock
        already paid chunk by chunk, interleaved with decode waves."""
        progressed = False
        for state in queue.prefilling():
            if state.prefill_chunks:
                state.prefill_chunks.pop(0)
                # the admission-time slice of the one full-prompt cost
                clock.charge_prefill(state.prefill_chunk_s.pop(0))
                progressed = True
            if not state.prefill_chunks:
                progressed |= self._finalize_prefill(state, cache_len,
                                                     clock, queue)
        return progressed

    def _finalize_prefill(self, state: RequestState, cache_len: int,
                          clock: DecodeClock,
                          queue: RequestQueue) -> bool:
        """Run the real prefill for a fully-charged chunked admission.
        Pool pages are claimed here; on a full pool the request simply
        stays in the prefilling set and retries as retirements free
        pages (its TTFT absorbs the wait, like a deferred admission)."""
        req = state.request
        if not self._pool_fits_prompt(req):
            return False
        eng = self.engine
        batch = {"tokens": jnp.asarray(req.prompt)[None, :]}
        token, cache_list, pos = eng.prefill_request(
            batch, cache_len, kv_pool=self.kv_pool,
            rid=req.rid if self.kv_pool is not None else None)
        state.token, state.cache_list, state.pos = token, cache_list, pos
        state.first_token_s = clock.now
        state.generated.append(int(token[0]))
        state.prefilling = False
        if eng.shadow is not None:
            state.shadow_state = self._shadow_start(batch, cache_len)
        if state.done:                       # max_new_tokens == 1
            state.finish_s = clock.now
            self._retire(state, queue)
        return True

    def _retire(self, state: RequestState, queue: RequestQueue) -> None:
        if self.kv_pool is not None:
            self.kv_pool.release(state.rid)
        queue.retire(state)

    # --------------------------------------------- KV preemption / resume
    def _preempt(self, state: RequestState, clock: DecodeClock) -> None:
        """Swap the victim's KV pages out to host and take it off the
        runnable set; the transfer serializes on the clock."""
        nbytes = self.kv_pool.swap_out(state.rid)
        state.preempted = True
        self._swap_s += clock.charge_kv_swap(nbytes)

    def _resume_preempted(self, queue: RequestQueue, clock: DecodeClock
                          ) -> bool:
        """Swap preempted requests back in, oldest admission first,
        while their full saved page sets fit (FIFO — a younger request
        never resumes past a starved older one)."""
        pool, resumed = self.kv_pool, False
        for state in queue.preempted():
            if not pool.can_alloc(pool.swapped_pages(state.rid)):
                break
            nbytes = pool.swap_in(state.rid)
            self._swap_s += clock.charge_kv_swap(nbytes)
            state.preempted = False
            resumed = True
        return resumed

    def _ensure_batch_pages(self, batch: List[RequestState],
                            queue: RequestQueue, clock: DecodeClock
                            ) -> List[RequestState]:
        """Hard budget guarantee before a composed step: every member
        gets the page its next slot writes into, preempting one
        runnable request (possibly a batch member, possibly the grower
        itself) per exhaustion via ``preemption_victim`` — youngest-
        first by default, most-deadline-slack-first under
        ``preempt="slack"``.  Each preemption strictly shrinks the
        runnable set, so the loop terminates: either the pool yields
        the pages or the grower itself is the last candidate and sits
        the step out."""
        pool = self.kv_pool
        for state in batch:
            if state.preempted:              # lost its pages to an older
                continue                     # member this very step
            # a verify wave may commit up to ``speculate`` new slots;
            # reserve conservatively (pages are monotonic anyway)
            need_slots = int(state.pos[0]) + self.speculate
            while True:
                try:
                    pool.ensure(state.rid, need_slots)
                    break
                except PoolExhausted:
                    victim = preemption_victim(queue.runnable(),
                                               self.preempt_policy,
                                               clock.now)
                    self._preempt(victim, clock)
                    if victim is state:
                        break
        return [s for s in batch if not s.preempted]

    # -------------------------------------------------------- shadow peek
    def _ensure_peeks(self, runnable: List[RequestState]) -> None:
        """Fleet-batched shadow peek: functionally step EVERY runnable
        request that lacks a cached peek as one composed shadow state —
        a single ``lm_decode`` dispatch per serving iteration instead of
        one per request.

        Per-request semantics are unchanged: token/KV alignment is
        applied to each request's own shadow state *before* composition
        (each request sees its own request-local iteration index), the
        composed step is sliced back per request, and the resulting peek
        is cached until the request actually takes that step
        (composition must not advance shadows — a request that sits out
        the next batch keeps its peek).

        With speculation (engine ``speculate=S``) the peek is a DRAFT
        ROLLOUT: the composed shadow steps ``S`` times (each step one
        batched dispatch), collecting per-step predictions, per-step
        snapshots (the rollback targets) and the draft tokens for wave
        positions 1..S-1.  After a wave commits ``c`` tokens the
        request's shadow lands on ``snapshots[c-1]`` — the state that
        consumed exactly the accepted tokens — so rejected drafts never
        survive in any shadow KV."""
        eng = self.engine
        if eng.shadow is None:
            return
        need = [s for s in runnable if s.pending is None]
        if not need:
            return
        with span("peek", rows=len(need)):
            aligned, flags = [], []
            for state in need:
                n = len(state.generated)      # request-local iteration index
                at = self.policy.align_token_at(n)
                ak = self.policy.align_kv_at(n)
                sh = state.shadow_state
                if ak:
                    sh = eng.shadow.align_kv_state(
                        sh, {"caches": eng._stack(state.cache_list),
                             "pos": state.pos})
                # the composed ``token`` field carries each request's chosen
                # shadow input (main token when aligning, else the shadow's)
                aligned.append(dict(sh, token=state.token if at
                                    else sh["token"]))
                flags.append((at, ak))
            composed = concat_shadow_states(aligned)
            preds_steps, snapshots = [], []
            st, tok = composed, composed["token"]
            for _ in range(self.speculate):
                preds, st = eng.shadow.step_state(st, tok)
                preds_steps.append(preds)
                snapshots.append(st)
                tok = st["token"]             # the shadow's greedy draft
            for i, (state, (at, ak)) in enumerate(zip(need, flags)):
                p_i = [{li: p[i:i + 1] for li, p in ps.items()}
                       for ps in preds_steps]
                s_i = [slice_shadow_state(s, i) for s in snapshots]
                drafts = (jnp.stack([s["token"][i:i + 1]
                                     for s in snapshots[:-1]], axis=1)
                          if self.speculate > 1
                          else jnp.zeros((1, 0), jnp.int32))
                state.pending = (p_i, s_i, at, ak, drafts)

    # --------------------------------------------------------------- run
    def start(self, requests: Sequence[Request], *,
              clock: Optional[DecodeClock] = None,
              cache_len: Optional[int] = None) -> None:
        """Set up a serving session without driving it: queue, clock and
        per-session counters.  ``run`` = start + tick-until-done +
        finish; a ``ClusterRouter`` instead interleaves ``tick`` calls
        across replicas (and feeds arrivals via ``add_request``),
        passing each replica its own ``clock`` (sharing one
        ``worker_free`` fleet timeline) and a cluster-wide
        ``cache_len``."""
        eng = self.engine
        install_gc_span()
        requests = list(requests)
        if cache_len is None:
            if not requests:
                raise ValueError("cache_len is required to start with an "
                                 "empty request set")
            cache_len = max(len(r.prompt) + r.max_new_tokens
                            for r in requests) + 2
        cache_len = self.max_seq_len or cache_len
        if self.kv_pool is not None:
            self.kv_pool.reset()
            # every request shares one page-aligned window (bit-exact vs
            # the dense path: the extra tail slots stay pos=-1/masked)
            cache_len = self.kv_pool.set_window(cache_len)
        self._cache_len = cache_len
        self._queue = RequestQueue(requests)
        self._clock = clock if clock is not None else DecodeClock(
            eng.cfg, eng.sched, self.profile,
            shadow_scheme=(eng.shadow.scheme if eng.shadow else "int8"),
            predictor=eng.predictor_kind,
            transport=getattr(eng, "transport", None),
            packed_compute=getattr(eng, "packed_slots", False))
        self._trace = Trace()
        self._steps = []
        self._deferred = _AdmissionQueue(self.admit_policy)
        self._admit_seq = 0
        self._swap_s = 0.0
        self._step = 0

    def add_request(self, req: Request) -> None:
        """Enqueue a request into a started session (cluster routing):
        it admits when the clock passes its arrival, exactly like an
        initial request."""
        self._queue.add(req)

    def has_work(self) -> bool:
        """True while the session still has anything to serve — the
        ``run`` loop condition, exposed so a cluster router can park
        idle replicas (their clock freezes until new work is routed)."""
        return not self._queue.all_done or bool(self._deferred)

    @property
    def clock(self) -> DecodeClock:
        return self._clock

    def memory_report(self) -> dict:
        """The engine's memory report, counting the session's live
        request state (caches, shadow states and peeks) as its caches."""
        live = [(s.cache_list, s.shadow_state, s.pending)
                for s in self._queue.active]
        return self.engine.memory_report(caches=live)

    def tick(self) -> bool:
        """One iteration of the serving loop (the body of ``run``'s
        while loop, verbatim).  Returns False when there is nothing
        left to do."""
        if not self.has_work():
            return False
        with span("tick", step=self._step):
            queue, clock = self._queue, self._clock
            deferred, cache_len = self._deferred, self._cache_len
            progressed = False
            if self.kv_pool is not None:
                progressed |= self._resume_preempted(queue, clock)
                while deferred and self._admission_fits(deferred.peek()):
                    self._admit_or_retire(deferred.pop(), cache_len,
                                          clock, queue)
                    progressed = True
            arrived = queue.pop_arrived(clock.now)
            if self.admit_policy == "priority":
                # weightiest tenant first; FIFO within a weight class
                arrived.sort(key=lambda r: (-r.weight, r.arrival_s,
                                            r.rid))
            for req in arrived:
                # budget-aware admission drains the deferred backlog in
                # the admission policy's order — strictly FIFO by
                # default: while an older request waits for pages,
                # younger arrivals queue behind it (mirrors the resume
                # path), otherwise a stream of small requests could
                # starve a large one.  Under "priority" the backlog is
                # weight-ordered instead, so interactive arrivals jump
                # deferred batch traffic.
                if deferred or not self._admission_fits(req):
                    self.kv_pool.stats.deferred_admissions += 1
                    deferred.push(req)
                    continue
                self._admit_or_retire(req, cache_len, clock, queue)
                progressed = True
            if self.prefill_chunk:
                progressed |= self._advance_prefills(queue, clock,
                                                     cache_len)
            runnable = queue.runnable()
            if not runnable:
                nxt = queue.next_arrival_s()
                if nxt is not None:
                    clock.advance_to(nxt)        # idle until the next arrival
                    return True
                if queue.all_done and not deferred:
                    return False
                if progressed:
                    return True                  # retires freed pages; retry
                raise RuntimeError(
                    "KV pool deadlock: nothing runnable, resumable or "
                    "admittable (pool smaller than one request window?)")
            self._ensure_peeks(runnable)
            batch = self.composer.compose(runnable)
            if self.kv_pool is not None:
                batch = self._ensure_batch_pages(batch, queue, clock)
                if not batch:
                    return True                  # preemptions freed pages
            self._decode_composed(batch, clock, self._trace, self._steps,
                                  self._step, queue.state_counts())
            for state in list(batch):
                if state.done:
                    state.finish_s = clock.now
                    self._retire(state, queue)
            self._step += 1
            return True

    def run(self, requests: Sequence[Request]) -> ServeResult:
        eng = self.engine
        if not requests:
            return ServeResult(outputs={}, timings=ServingTimings(
                [], [], [], []), trace=Trace(),
                n_workers=eng.sched.n_workers)
        self.start(requests)
        while self.tick():
            pass
        return self.finish()

    def finish(self) -> ServeResult:
        """Close a served session: collect kv/prefetch/spec stats and
        build the ``ServeResult`` (the tail of the historical ``run``)."""
        eng, queue = self.engine, self._queue
        kv_stats = None
        if self.kv_pool is not None:
            kv_stats = self.kv_pool.stats.as_dict()
            kv_stats.update(swap_s=self._swap_s,
                            num_pages=self.kv_pool.num_pages,
                            page_tokens=self.kv_pool.page_tokens,
                            pool_bytes=self.kv_pool.pool_bytes())
        prefetch_stats = (eng.prefetch_report()
                          if (eng.prefetch is not None
                              or eng.residency is not None) else None)
        spec_stats = None
        if self.speculate > 1:
            per = {rid: {"waves": s.spec_waves,
                         "committed": s.spec_committed,
                         "acceptance": (s.spec_committed
                                        / (s.spec_waves * self.speculate)
                                        if s.spec_waves else 0.0)}
                   for rid, s in sorted(queue.finished.items())}
            tw = sum(v["waves"] for v in per.values())
            tc = sum(v["committed"] for v in per.values())
            spec_stats = {"speculate": self.speculate, "waves": tw,
                          "committed": tc,
                          "acceptance": (tc / (tw * self.speculate)
                                         if tw else 0.0),
                          "per_request": per}
        return self._result(queue, self._trace, self._steps,
                            eng.sched.n_workers, kv_stats, prefetch_stats,
                            spec_stats)

    # ------------------------------------------------------ composed step
    def _decode_composed(self, batch: List[RequestState],
                         clock: DecodeClock, trace: Trace,
                         steps: List[StepRecord], step: int,
                         queue_counts: Optional[Dict[str, int]] = None
                         ) -> None:
        """One composed iteration: a classic one-token step when
        ``speculate == 1``, else one draft-verify-accept wave.  Requests
        commit INDEPENDENT accepted prefixes (capped by their remaining
        token budgets); each lands its shadow on the snapshot matching
        its own commit, so a rejection invalidates exactly that
        request's unconsumed drafts and nothing else."""
        eng = self.engine
        S = self.speculate
        pos = jnp.concatenate([s.pos for s in batch])
        with span("kv_gather", rows=len(batch)):
            caches = concat_cache_lists([s.cache_list for s in batch])
        preds: Dict[int, np.ndarray] = {}
        at = ak = False
        if eng.shadow is not None:
            # wave-row order b*S + s (== batch order for S == 1)
            per_req = [wave_preds(s.pending[0]) for s in batch]
            for li in per_req[0]:
                preds[li] = np.concatenate([p[li] for p in per_req])
            at = any(s.pending[2] for s in batch)
            ak = any(s.pending[3] for s in batch)
        if S > 1:
            # column 0 the true last token, columns 1.. the drafts
            tokens = jnp.concatenate(
                [jnp.concatenate([s.token[:, None],
                                  s.pending[4].astype(jnp.int32)], axis=1)
                 for s in batch])
            budget = jnp.asarray(
                [s.request.max_new_tokens - len(s.generated)
                 for s in batch], jnp.int32)
        else:
            tokens = jnp.concatenate([s.token for s in batch])[:, None]
            budget = None
        # index == the engine step counter (also what fault events and
        # trace replays compare against), exactly as in generate()
        rec = TokenRecord(index=step, aligned_token=at, aligned_kv=ak)
        eng.slots.set_request_context([s.rid for s in batch])
        start = clock.now
        verified, commits, caches, pos = eng.decode_batch_spec(
            tokens, caches, pos, preds, step, rec, max_commit=budget)
        eng.slots.set_request_context(())
        with span("model_clock"):
            duration, stall = clock.step(rec)
        trace.records.append(rec)
        steps.append(StepRecord(step=step,
                                request_ids=[s.rid for s in batch],
                                record=rec, start_s=start,
                                duration_s=duration, stall_s=stall,
                                alive_workers=clock.alive_workers(),
                                kv_pages_used=(self.kv_pool.pages_used
                                               if self.kv_pool is not None
                                               else -1),
                                queue_counts=queue_counts))
        sl = rec.spec_len                     # wave rows per request
        # the token readbacks wait for the step's last device work
        with span("commit", rows=len(batch)):
            for i, state in enumerate(batch):
                ci = int(commits[i])
                state.token = verified[i, ci - 1:ci]
                with span("kv_scatter"):
                    state.cache_list = slice_cache_list(caches, i)
                state.pos = pos[i:i + 1]
                state.generated.extend(int(t) for t in verified[i, :ci])
                if state.pending is not None:
                    # rollback to the snapshot that consumed exactly the
                    # accepted tokens — the peek's rejected drafts die here
                    state.shadow_state = state.pending[1][ci - 1]
                state.pending = None
                state.spec_waves += 1
                state.spec_committed += ci
                lo = i * sl                       # this request's wave rows;
                #                                   only accepted ones count
                state.last_experts = frozenset(
                    (lr.layer, int(e)) for lr in rec.layers
                    for e in lr.true[lo:lo + ci].reshape(-1))
                sliced = self._slice_record(rec, lo, lo + ci)
                sliced.index = len(state.generated) - ci  # wave-start n
                state.trace.records.append(sliced)
                if eng.keep_logits:
                    state.trace.logits.extend(
                        eng.last_logits[lo + j:lo + j + 1] for j in range(ci))

    @staticmethod
    def _slice_record(rec: TokenRecord, lo: int, hi: int) -> TokenRecord:
        """One request's view of a composed record: its accepted wave
        rows ``lo:hi`` (a single row for non-speculative steps).
        Loads/reloads are shared across the batch, so per-request
        records carry routing and recall only (reloads=0,
        assignments=[]); load accounting lives in the composed-step
        trace and the worker-slot event log."""
        out = TokenRecord(index=rec.index, aligned_token=rec.aligned_token,
                          aligned_kv=rec.aligned_kv, spec_len=hi - lo,
                          committed=hi - lo)
        for lr in rec.layers:
            pred_i = None if lr.predicted is None else lr.predicted[lo:hi]
            true_i = lr.true[lo:hi]
            out.layers.append(LayerRecord(
                layer=lr.layer, moe_index=lr.moe_index, group=lr.group,
                predicted=pred_i, true=true_i,
                correct=(recall_counts(pred_i, true_i)
                         if pred_i is not None else 0),
                reloads=0, assignments=[],
                gates=None if lr.gates is None else lr.gates[lo:hi]))
        return out

    # ------------------------------------------------------------ result
    @staticmethod
    def _result(queue: RequestQueue, trace: Trace,
                steps: List[StepRecord], n_workers: int,
                kv_stats: Optional[Dict] = None,
                prefetch_stats: Optional[Dict] = None,
                spec_stats: Optional[Dict] = None) -> ServeResult:
        states = dict(sorted(queue.finished.items()))
        timings = ServingTimings(
            arrival_s=[s.request.arrival_s for s in states.values()],
            first_token_s=[s.first_token_s for s in states.values()],
            finish_s=[s.finish_s for s in states.values()],
            tokens=[len(s.generated) for s in states.values()],
            tenants=[s.request.tenant for s in states.values()],
            ttft_slo_s=[s.request.ttft_slo_s for s in states.values()],
            tpot_slo_s=[s.request.tpot_slo_s for s in states.values()])
        outputs = {rid: np.asarray(s.generated, np.int32)
                   for rid, s in states.items()}
        return ServeResult(outputs=outputs, timings=timings, trace=trace,
                           steps=steps, states=states, n_workers=n_workers,
                           kv_stats=kv_stats, prefetch_stats=prefetch_stats,
                           spec_stats=spec_stats)
