"""An engine that loads physically and is given no executor fetches its
predicted experts ahead, on the process's one loader pool: the same
tokens, event log, bytes and stats as the inline ``prefetch="sync"``
oracle, no more than ``peek_horizon`` MoE layers of predictions in
flight, none beyond what the fleet's slots can take, and no thread of
its own."""
import contextlib
import functools
import sys
import threading

import jax
import numpy as np
import pytest

from conftest import tiny_moe
from repro.core import (ODMoEEngine, SyncExecutor, ThreadedExecutor,
                        moe_layer_indices)
from repro.core.engine import PEEK_HORIZON
from repro.core import prefetch as prefetch_mod
from repro.core import store as store_mod
from repro.core.prefetch import LOADER_THREADS, loader_pool
from repro.models import greedy_generate, init_params

N_TOK = 6


@functools.lru_cache(maxsize=None)
def _model():
    cfg = tiny_moe(num_layers=6)        # more MoE layers than the window
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size), np.int32)
    return cfg, params, tokens


def _decode(n_tok=N_TOK, **kw):
    cfg, params, tokens = _model()
    eng = ODMoEEngine(cfg, params, **{"n_workers": 8, "predictor": "sep",
                                      **kw})
    toks, eng.last_trace = eng.generate({"tokens": tokens}, n_tok)
    events = tuple((e.token, e.layer, e.expert, e.worker, e.predicted,
                    e.bytes, e.scheme) for e in eng.slots.events)
    return eng, np.asarray(toks), events


def test_default_physical_engine_fetches_ahead():
    eng, _, _ = _decode()
    assert isinstance(eng.prefetch.executor, ThreadedExecutor)
    assert eng.prefetch.horizon == PEEK_HORIZON
    rep = eng.prefetch_report()
    assert rep["executor"] == "thread"
    # every predicted load committed a prefetched payload; only the
    # reloads were fetched on demand, and nothing loaded inline
    assert rep["prefetch_prefetched"] == \
        eng.slots.stats["predicted_loads"] > 0
    assert rep["prefetch_inline"] == 0
    assert rep["prefetch_demand_fetches"] == eng.slots.stats["reloads"]


def test_default_matches_the_sync_oracle():
    cfg, params, tokens = _model()
    ref = np.asarray(greedy_generate(cfg, params, {"tokens": tokens},
                                     N_TOK))
    sync, s_toks, s_events = _decode(prefetch="sync")
    ahead, a_toks, a_events = _decode()
    assert isinstance(sync.prefetch.executor, SyncExecutor)
    np.testing.assert_array_equal(s_toks, ref)
    np.testing.assert_array_equal(a_toks, ref)
    assert a_events == s_events
    assert ahead.slots.bytes_moved == sync.slots.bytes_moved
    assert ahead.slots.stats == sync.slots.stats


@pytest.mark.parametrize("kw", [dict(physical_loading=False),
                                dict(wave_compute="loop")])
def test_no_executor_where_nothing_is_hidden(kw):
    cfg, params, _ = _model()
    assert ODMoEEngine(cfg, params, n_workers=8, **kw).prefetch is None


@pytest.mark.parametrize("horizon", [1, 2, 3])
def test_in_flight_fetches_stay_within_the_horizon(horizon):
    """Fetches submitted and not yet joined or discarded never span
    more than ``peek_horizon`` MoE layers of one step."""
    cfg, params, tokens = _model()
    eng = ODMoEEngine(cfg, params, n_workers=8, peek_horizon=horizon)
    ex = eng.prefetch.executor
    live, widest = set(), [0]
    submit, collect, discard = ex.submit, ex.collect, ex.discard

    def track_submit(key, fn):
        submit(key, fn)
        live.add(key)
        widest[0] = max(widest[0], len({k[:2] for k in live}))

    def track_collect(keys):
        live.difference_update(keys)
        return collect(keys)

    def track_discard(keys):
        live.difference_update(keys)
        return discard(keys)

    ex.submit, ex.collect, ex.discard = (track_submit, track_collect,
                                         track_discard)
    eng.generate({"tokens": tokens}, N_TOK)
    assert widest[0] == min(horizon, len(moe_layer_indices(cfg)))


def test_fetch_ahead_stops_at_the_fleet(monkeypatch):
    """Two rows of top-2 predict up to four experts a layer for two
    workers.  Only the experts the schedule places are fetched ahead;
    the overflow reloads on demand.  So every fetch commits, and the
    fetches' spans match the counters as the inline oracle's do."""
    log = []

    @contextlib.contextmanager
    def record(name, **args):
        yield
        log.append((name, threading.current_thread(), args))

    monkeypatch.setattr(prefetch_mod, "span", record)
    monkeypatch.setattr(store_mod, "span", record)
    sync, s_toks, s_events = _decode(n_workers=2, prefetch="sync")
    log.clear()
    eng, toks, events = _decode(n_workers=2)
    np.testing.assert_array_equal(toks, s_toks)
    assert events == s_events
    assert eng.slots.stats == sync.slots.stats
    unique = [len(np.unique(lr.predicted))
              for r in eng.last_trace.records for lr in r.layers]
    assert max(unique) > 2 and eng.slots.stats["reloads"] > 0
    assert eng.prefetch.stats["submitted"] == sum(min(n, 2) for n in unique)
    assert eng.prefetch.stats["submitted"] == \
        eng.slots.stats["predicted_loads"]
    loads = [a for n, _, a in log if n == "expert_load"]
    assert len(loads) == eng.slots.stats["loads"]
    assert sum(a["nbytes"] for a in loads) == eng.slots.bytes_moved
    assert all(a["ahead"] == a["predicted"] for a in loads)
    assert sum(a["ahead"] for a in loads) == \
        eng.slots.stats["predicted_loads"]
    main = threading.main_thread()
    assert all(t is not main for n, t, _ in log if n == "expert_load")
    assert all(t is main for n, t, _ in log if n == "expert_wait")


def _loader_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("loader")]


def test_engines_share_one_loader_pool():
    first, _, _ = _decode(n_tok=3)
    assert 0 < len(_loader_threads()) <= LOADER_THREADS
    first.close()                  # drops its fetches, not the pool
    engines = [_decode(n_tok=3)[0] for _ in range(20)]
    assert all(e.prefetch.executor._pool is loader_pool()
               for e in engines)
    assert len(_loader_threads()) <= LOADER_THREADS


def test_executors_on_many_threads_keep_their_own_fetches():
    """Engines on several threads (replicas behind a router) share the
    pool: each executor gets back exactly its own payloads, and one
    closing drops none of the others'."""
    n_threads, n_keys = 12, 40
    out, errors = {}, []

    def drive(i):
        try:
            ex = ThreadedExecutor()
            keys = [(0, li, e) for li in range(4) for e in range(n_keys // 4)]
            for k in keys:
                ex.submit(k, lambda k=k: (i, k))
            got = ex.collect(keys[::2])
            ex.close()                 # drops the odd keys
            out[i] = got == {k: (i, k) for k in keys[::2]}
        except Exception as e:         # reported below, on the main thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert out == {i: True for i in range(n_threads)}
    assert len(_loader_threads()) <= LOADER_THREADS
