"""Seeded concurrency-chaos harness for the async prefetch executor.

The contract under test (the repo's load-bearing invariant, extended to
the first genuinely concurrent path): an async engine's **tokens, load
events, and byte accounting** are bit-identical to the synchronous
engine with the same fault script / residency / transport config — and
its tokens bit-identical to ``greedy_generate(..., transport=policy)``
— under EVERY executor schedule.  ``ChaosExecutor`` supplies the
adversarial schedules: seeded permuted completion orders, early runs,
injected delays (deferred tasks) and dropped transfers, on top of
scripted mid-wave fleet faults.

Reproducing a failure: every assertion message prints the scenario
seed.  ``ChaosExecutor(seed)`` plus the seed-derived scenario in
``_scenario(seed)`` deterministically replays the identical schedule:

    CHAOS_REPRO=<seed> pytest tests/test_prefetch_chaos.py -k repro -s

Seed budget: ``range(N_FAST)`` runs in the fast tier;
``range(N_FAST, N_FAST + 175 * CHAOS_SEED_MULT)`` rides the slow tier
(the nightly job sets ``CHAOS_SEED_MULT=20`` to hunt rare
interleavings off the PR critical path).  Per PR that totals 200
distinct engine-level schedules, plus the executor-level hypothesis
properties below.
"""
import functools
import os
import random

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import tiny_moe
from repro.core import (ChaosExecutor, ODMoEEngine, PrefetchExecutor,
                        SyncExecutor, ThreadedExecutor,
                        layers_within_horizon)
from repro.fleet import FaultEvent, FaultInjector, outage, \
    random_fault_script
from repro.models import greedy_generate, init_params

N_TOK = 5
N_FAST = 25
SEED_MULT = int(os.environ.get("CHAOS_SEED_MULT", "1"))
SLOW_SEEDS = range(N_FAST, N_FAST + 175 * SEED_MULT)

# scenario building blocks: scripted faults (step-scoped outages and
# mid-wave kills — the stranded-predicted-load window), each pinned to
# a predictor and transport so the sync-baseline cache stays small
SCRIPTS = {
    "calm": ([], "sep", None),
    "outage": (outage(1, 2) + outage(5, 3, 5), "freq", None),
    "midwave": ([FaultEvent(2, 0, "kill", moe_index=1),
                 FaultEvent(3, 2, "kill", moe_index=3),
                 FaultEvent(4, 0, "recover")], "sep", "int8"),
    "storm": (random_fault_script(123, 8, N_TOK, 4), "freq", None),
}
RESIDENCIES = (None, "lru", "gate")


@functools.lru_cache(maxsize=None)
def _model():
    cfg = tiny_moe()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch_tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                           cfg.vocab_size), np.int32)
    return cfg, params, batch_tokens


@functools.lru_cache(maxsize=None)
def _reference_tokens(transport):
    cfg, params, tokens = _model()
    return np.asarray(greedy_generate(cfg, params, {"tokens": tokens},
                                      N_TOK, transport=transport))


def _snapshot(script_key, residency, transport, predictor, executor=None):
    """One engine decode; returns everything the invariant pins."""
    cfg, params, tokens = _model()
    eng = ODMoEEngine(cfg, params, n_workers=8, predictor=predictor,
                      transport=transport, residency=residency,
                      faults=FaultInjector(SCRIPTS[script_key][0]),
                      prefetch=executor)
    try:
        toks, trace = eng.generate({"tokens": tokens}, N_TOK)
    finally:
        eng.close()
    event_log = tuple((e.token, e.layer, e.expert, e.worker, e.predicted,
                       e.bytes, e.scheme) for e in eng.slots.events)
    return (np.asarray(toks), event_log, eng.slots.bytes_moved,
            dict(eng.slots.stats), dict(eng.slots.residency_stats))


@functools.lru_cache(maxsize=None)
def _baseline(script_key, residency):
    """The synchronous oracle for one scenario config (inline fetches)."""
    _, predictor, transport = SCRIPTS[script_key]
    return _snapshot(script_key, residency, transport, predictor, "sync")


def _scenario(seed):
    """Everything about a chaos case derives deterministically from its
    seed — print the seed, replay the schedule."""
    rng = random.Random(seed)
    script_key = rng.choice(sorted(SCRIPTS))
    residency = rng.choice(RESIDENCIES)
    executor = ChaosExecutor(seed,
                             p_run_ahead=rng.uniform(0.0, 1.0),
                             p_drop=rng.uniform(0.0, 0.5),
                             p_defer=rng.uniform(0.0, 0.5))
    return script_key, residency, executor


def _check_schedule(seed):
    script_key, residency, executor = _scenario(seed)
    _, predictor, transport = SCRIPTS[script_key]
    why = (f"chaos seed={seed} (script={script_key!r}, "
           f"residency={residency!r}, transport={transport!r}; replay "
           f"with _scenario({seed}))")
    toks, events, nbytes, stats, rstats = _snapshot(
        script_key, residency, transport, predictor, executor)
    b_toks, b_events, b_bytes, b_stats, b_rstats = _baseline(
        script_key, residency)
    ref = _reference_tokens(transport)
    assert np.array_equal(toks, ref), f"tokens diverged from greedy: {why}"
    assert np.array_equal(toks, b_toks), f"tokens diverged from sync: {why}"
    assert events == b_events, f"event log diverged: {why}"
    assert nbytes == b_bytes, f"bytes_moved diverged: {why}"
    assert stats == b_stats, f"slot stats diverged: {why}"
    assert rstats == b_rstats, f"residency stats diverged: {why}"


@pytest.mark.parametrize("seed", range(N_FAST))
def test_chaos_schedule(seed):
    """Fast-tier slice of the seeded-schedule sweep."""
    _check_schedule(seed)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SLOW_SEEDS)
def test_chaos_schedule_slow(seed):
    """The remainder of the per-PR 200-schedule budget; the nightly job
    multiplies it via ``CHAOS_SEED_MULT``."""
    _check_schedule(seed)


def test_chaos_repro_env():
    """Replay one schedule from an explicitly printed seed:
    ``CHAOS_REPRO=<seed> pytest -k repro``."""
    seed = int(os.environ.get("CHAOS_REPRO", "0"))
    _check_schedule(seed)


def test_chaos_schedules_are_distinct():
    """The sweep genuinely varies the schedule: different seeds produce
    different executor journals (no degenerate all-identical sweep)."""
    logs = set()
    for seed in range(10):
        script_key, residency, ex = _scenario(seed)
        _, predictor, transport = SCRIPTS[script_key]
        _snapshot(script_key, residency, transport, predictor, ex)
        logs.add(tuple(ex.log))
    assert len(logs) >= 9


# ----------------------------------------------------- speculative chaos
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_chaos_spec_schedule(seed):
    """Speculative verify waves under chaos schedules + mid-run faults:
    tokens stay bit-identical to greedy, and the async run matches the
    synchronous speculative engine on tokens, event log, byte
    accounting and per-wave acceptance."""
    cfg, params, tokens = _model()
    rng = random.Random(seed + 500)
    k = rng.choice([2, 4])
    residency = rng.choice(RESIDENCIES)
    faults = random_fault_script(seed + 500, 8, N_TOK, 3)

    def run(executor):
        eng = ODMoEEngine(cfg, params, n_workers=8, speculate=k,
                          residency=residency,
                          faults=FaultInjector(faults), prefetch=executor)
        try:
            toks, trace = eng.generate({"tokens": tokens}, N_TOK)
        finally:
            eng.close()
        log = tuple((e.token, e.layer, e.expert, e.worker, e.predicted,
                     e.bytes) for e in eng.slots.events)
        commits = tuple(r.committed for r in trace.records)
        return np.asarray(toks), log, eng.slots.bytes_moved, commits

    base = run("sync")
    chaos = run(ChaosExecutor(seed + 500, p_drop=0.3, p_defer=0.3))
    why = (f"spec chaos seed={seed} k={k} residency={residency!r}; "
           f"replay with seed+500={seed + 500}")
    ref = _reference_tokens(None)
    assert np.array_equal(base[0], ref), f"sync spec vs greedy: {why}"
    assert np.array_equal(chaos[0], ref), f"async spec vs greedy: {why}"
    assert base[1] == chaos[1], f"event log diverged: {why}"
    assert base[2] == chaos[2], f"bytes diverged: {why}"
    assert base[3] == chaos[3], f"acceptance diverged: {why}"


# -------------------------------------------------- packed-slots chaos
@functools.lru_cache(maxsize=None)
def _packed_model():
    """64-aligned expert width so nf4 takes the tile-aligned packed
    path (the default tiny_moe's d_expert=96 covers the fallback)."""
    cfg = tiny_moe(num_layers=3, d_expert=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch_tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                           cfg.vocab_size), np.int32)
    return cfg, params, batch_tokens


@functools.lru_cache(maxsize=None)
def _packed_reference(scheme):
    cfg, params, tokens = _packed_model()
    return np.asarray(greedy_generate(cfg, params, {"tokens": tokens},
                                      N_TOK, transport=scheme))


@pytest.mark.parametrize("scheme", ["int8", "nf4"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_packed_slots(scheme, seed):
    """ISSUE 10 acceptance gate: packed-resident slots + the fused
    in-kernel-dequant grouped path stay token-bit-identical to
    ``greedy_generate(..., transport=policy)`` under chaos schedules
    and mid-run faults, and match the dequantize-on-arrival engine's
    event log exactly — only the eviction byte pricing (packed vs full
    width) may differ."""
    cfg, params, tokens = _packed_model()
    rng = random.Random(seed + 2000)
    residency = rng.choice(RESIDENCIES)
    faults = random_fault_script(seed + 2000, 8, N_TOK, 3)

    def run(packed, executor=None):
        eng = ODMoEEngine(cfg, params, n_workers=8, predictor="sep",
                          transport=scheme, residency=residency,
                          faults=FaultInjector(faults),
                          prefetch=executor, packed_slots=packed)
        try:
            toks, _ = eng.generate({"tokens": tokens}, N_TOK)
        finally:
            eng.close()
        log = tuple((e.token, e.layer, e.expert, e.worker, e.predicted,
                     e.bytes, e.scheme) for e in eng.slots.events)
        return (np.asarray(toks), log, eng.slots.bytes_moved,
                dict(eng.slots.stats), eng.slots.device_bytes_per_worker())

    why = (f"packed chaos scheme={scheme} seed={seed} "
           f"residency={residency!r}")
    sync = run(True, "sync")
    chaos = run(True, ChaosExecutor(seed + 2000, p_run_ahead=0.5,
                                    p_drop=0.3, p_defer=0.3))
    base = run(False, "sync")
    ref = _packed_reference(scheme)
    assert np.array_equal(sync[0], ref), f"sync vs greedy: {why}"
    assert np.array_equal(chaos[0], ref), f"chaos vs greedy: {why}"
    assert sync[1] == chaos[1] == base[1], f"event log diverged: {why}"
    assert sync[2] == chaos[2] == base[2], f"bytes diverged: {why}"
    assert sync[3] == chaos[3] == base[3], f"stats diverged: {why}"
    assert sync[4] == chaos[4] < base[4], \
        f"packed footprint not below fp32-slot baseline: {why}"


# --------------------------------------------------- serving-loop chaos
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_serving_chaos_schedule(seed):
    """Continuous batching over the async engine: per-request outputs,
    the shared event log and byte accounting all match the synchronous
    serving baseline under chaos schedules + mid-run faults."""
    from repro.core import RTX3090_EDGE
    from repro.serve import Request, ServingLoop

    cfg, params, _ = _model()
    rng = random.Random(seed)
    residency = rng.choice(RESIDENCIES)
    faults = random_fault_script(seed + 1000, 8, 6, 4)

    def serve(executor):
        reqs = [Request(rid=i, prompt=list(range(1, 7 + i)),
                        max_new_tokens=4, arrival_s=0.01 * i)
                for i in range(4)]
        eng = ODMoEEngine(cfg, params, n_workers=8, residency=residency,
                          faults=FaultInjector(faults), prefetch=executor)
        try:
            res = ServingLoop(eng, max_batch=3,
                              profile=RTX3090_EDGE).run(reqs)
        finally:
            eng.close()
        log = tuple((e.token, e.layer, e.expert, e.worker, e.predicted,
                     e.bytes, e.requests) for e in eng.slots.events)
        return res, log, eng.slots.bytes_moved

    base, b_log, b_bytes = serve("sync")
    chaos, c_log, c_bytes = serve(ChaosExecutor(seed, p_drop=0.3,
                                                p_defer=0.3))
    why = f"serving chaos seed={seed} residency={residency!r}"
    assert sorted(base.outputs) == sorted(chaos.outputs), why
    for rid in base.outputs:
        assert np.array_equal(base.outputs[rid], chaos.outputs[rid]), \
            f"request {rid} diverged: {why}"
    assert b_log == c_log, f"event log diverged: {why}"
    assert b_bytes == c_bytes, f"bytes diverged: {why}"
    assert chaos.prefetch_stats is not None


# ------------------------------------------- executor-level properties
class _StubStore:
    """Payload = (layer, expert, device) — enough to pin that executors
    deliver exactly the fetch result, untouched, for the right key."""

    def unpack_shard(self, layer, expert, device=True):
        return (layer, expert, device)

    def packed_bytes(self, layer, expert):
        return 0


def _drive(executor, rng, journal=None):
    """One deterministic random call sequence against an executor;
    returns the delivered payload map."""
    delivered = {}
    live = []
    for _ in range(30):
        op = rng.random()
        if op < 0.5 or not live:
            key = (rng.randint(0, 3), rng.randint(0, 7), rng.randint(0, 7))
            executor.submit(key, lambda k=key: ("payload", k))
            live.append(key)
        elif op < 0.85:
            demanded = [live.pop(rng.randrange(len(live)))
                        for _ in range(min(len(live), rng.randint(1, 3)))]
            got = executor.collect(demanded)
            for k, v in got.items():
                assert k in demanded
                delivered[k] = v
        else:
            executor.discard([live.pop(rng.randrange(len(live)))])
    if journal is not None:
        journal.append(tuple(getattr(executor, "log", ())))
    return delivered


@given(seed=st.integers(min_value=0, max_value=10**9))
@settings(max_examples=80)
def test_chaos_executor_deterministic(seed):
    """Same seed + same call sequence => identical schedule journal and
    identical deliveries — the property that makes every chaos failure
    reproducible from its printed seed."""
    runs = []
    journals = []
    for _ in range(2):
        runs.append(_drive(ChaosExecutor(seed), random.Random(seed + 1),
                           journals))
    assert runs[0] == runs[1], f"seed={seed}"
    assert journals[0] == journals[1], f"seed={seed}"


@given(seed=st.integers(min_value=0, max_value=10**9))
@settings(max_examples=80)
def test_executors_deliver_correct_payloads(seed):
    """Whatever the schedule, a delivered payload is the fetch result
    for ITS key — never another task's, never mutated."""
    for make in (SyncExecutor, lambda: ChaosExecutor(seed)):
        delivered = _drive(make(), random.Random(seed))
        for k, v in delivered.items():
            assert v == ("payload", k), f"seed={seed}"


@given(seed=st.integers(min_value=0, max_value=10**6))
@settings(max_examples=25)
def test_prefetch_queue_accounting(seed):
    """With the degenerate sync executor every demanded enqueued key is
    delivered, payloads come from the store, and the stale sweep
    retires exactly what was never demanded."""
    rng = random.Random(seed)
    pf = PrefetchExecutor(_StubStore(), SyncExecutor(), physical=False)
    pending = {li: np.asarray([[rng.randint(0, 7), rng.randint(0, 7)]])
               for li in (1, 3, 5, 7)}
    pf.enqueue(0, 0, pending)
    demanded = sorted({int(e) for e in pending[3].reshape(-1)})
    got = pf.collect(0, 3, demanded)
    assert sorted(got) == demanded
    for e, payload in got.items():
        assert payload == (3, e, False)
    pf.finish_token(0)
    assert pf.stats["prefetched"] == len(demanded)
    assert pf.stats["submitted"] == (pf.stats["prefetched"]
                                     + pf.stats["stale"])
    assert not pf._enqueued


@given(cur=st.integers(min_value=0, max_value=12),
       horizon=st.integers(min_value=0, max_value=6))
@settings(max_examples=40)
def test_peek_horizon_window(cur, horizon):
    layers = [1, 3, 5, 7, 9, 11]
    win = layers_within_horizon(layers, cur, horizon)
    ahead = [li for li in layers if li >= cur]
    assert win == (ahead if horizon == 0 else ahead[:horizon])


def test_threaded_executor_delivers():
    """Real threads: submitted fetches complete and join correctly (the
    bit-exactness of the full engine path is pinned above; this pins
    the executor plumbing in isolation, including discard)."""
    ex = ThreadedExecutor()
    try:
        keys = [(0, li, e) for li in range(3) for e in range(4)]
        for k in keys:
            ex.submit(k, lambda k=k: ("payload", k))
        got = ex.collect(keys[:6])
        assert got == {k: ("payload", k) for k in keys[:6]}
        assert ex.discard(keys[6:]) == 6
        assert ex.collect(keys[6:]) == {}
    finally:
        ex.close()


def test_prefetch_requires_grouped_path():
    cfg, params, _ = _model()
    with pytest.raises(ValueError):
        ODMoEEngine(cfg, params, wave_compute="loop", prefetch="sync")
    with pytest.raises(ValueError):
        ODMoEEngine(cfg, params, wave_compute="loop", residency="lru")
