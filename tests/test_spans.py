"""The serving path's ``odmoe.*`` host spans, read back from a profiler
trace on the CPU: every phase appears, with its arguments, nested on the
thread that opened it (expert fetches on the loader threads, their joins
on the decode thread), and tracing changes no served token."""
import gc
import glob
import os

import jax
import numpy as np
import pytest

from conftest import tiny_moe
from repro.core import ODMoEEngine, moe_layer_indices
from repro.models import init_params
from repro.serve import Request, ServingLoop

CFG = tiny_moe(num_layers=3)

SPANS = ("tick", "admit", "prefill", "peek", "shadow_step", "kv_gather",
         "decode_step", "router_sync", "serve", "expert_load",
         "expert_wait", "wave", "commit", "kv_scatter", "model_clock",
         "gc")


def _requests():
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=rng.integers(0, CFG.vocab_size, n
                                               ).astype(np.int32),
                    max_new_tokens=m, arrival_s=0.0)
            for i, (n, m) in enumerate([(6, 4), (9, 3), (5, 4)])]


def _serve(params):
    eng = ODMoEEngine(CFG, params, n_workers=4, predictor="sep",
                      shadow_scheme="int8")
    res = ServingLoop(eng, max_batch=2).run(_requests())
    return eng, res


def _events(log_dir):
    """odmoe.* events of the trace: (name, start, end, line, args)."""
    from jax.profiler import ProfileData
    (f,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    out = []
    for pi, plane in enumerate(ProfileData.from_file(f).planes):
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("odmoe."):
                    out.append((e.name[len("odmoe."):], e.start_ns,
                                e.start_ns + e.duration_ns, (pi, li),
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    params = init_params(CFG, jax.random.PRNGKey(0))
    _, plain = _serve(params)                  # profiler off (and warm)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        eng, traced = _serve(params)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    return plain, traced, eng, _events(log_dir)


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parents):
    return any(p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


def test_every_span_appears(served):
    _, _, _, events = served
    assert {e[0] for e in events} >= set(SPANS)
    gcs = _named(events, "gc")
    assert any(e[4]["generation"] == 2 and "collected" in e[4]
               for e in gcs)


def test_router_sync_once_per_moe_layer_and_step(served):
    _, res, _, events = served
    syncs = _named(events, "router_sync")
    assert len(syncs) == len(moe_layer_indices(CFG)) * len(res.steps)
    steps = _named(events, "decode_step")
    assert len(steps) == len(res.steps)
    assert [e[4]["rows"] for e in sorted(steps, key=lambda e: e[1])] == \
        [len(s.request_ids) for s in res.steps]
    assert str(steps[0][4]["rids"]).count(";") + 1 == steps[0][4]["rows"]


def _decode_threads(events):
    return {e[3] for e in _named(events, "decode_step")}


def test_expert_loads_match_the_counters(served):
    _, _, eng, events = served
    loads = _named(events, "expert_load")
    assert len(loads) == eng.slots.stats["loads"]
    assert sum(e[4]["nbytes"] for e in loads) == eng.slots.bytes_moved
    assert sum(e[4]["predicted"] for e in loads) == \
        eng.slots.stats["predicted_loads"]
    # the fetches ran on the loader threads, not the decode thread
    assert {e[3] for e in loads}.isdisjoint(_decode_threads(events))


def test_ahead_marks_the_predicted_fetches(served):
    """Every predicted expert was fetched ahead of its layer (the SEP
    shadow predicts the whole token before the step); reloads are
    demand fetches."""
    _, _, eng, events = served
    loads = _named(events, "expert_load")
    assert all(e[4]["ahead"] == e[4]["predicted"] for e in loads)
    assert sum(e[4]["ahead"] for e in loads) == \
        eng.slots.stats["predicted_loads"] > 0


def test_expert_waits_nest_inside_serve_on_the_decode_thread(served):
    _, _, _, events = served
    waits, serves = _named(events, "expert_wait"), _named(events, "serve")
    assert waits
    assert all(_inside(w, serves) for w in waits)
    assert {w[3] for w in waits} <= _decode_threads(events)
    assert all(0 <= w[4]["ready"] <= w[4]["experts"] for w in waits)


def test_fetches_run_between_their_submit_and_their_join(served):
    """A fetch on a loader thread ends before the decode thread's join
    in the layer that needs it ends, and starts after whatever submitted
    it began: the decode step for a fetch ahead, the layer's own
    ``odmoe.serve`` for a reload's demand fetch."""
    _, _, _, events = served
    decode = _decode_threads(events)
    steps, serves = _named(events, "decode_step"), _named(events, "serve")
    waits = _named(events, "expert_wait")
    fetches = [e for e in _named(events, "expert_load")
               if e[3] not in decode]
    assert fetches
    for f in fetches:
        # the first serve of the fetch's layer that ends after it
        serve = min((s for s in serves
                     if s[4]["layer"] == f[4]["layer"] and s[2] >= f[2]),
                    key=lambda s: s[2])
        joins = [w for w in waits if _inside(w, [serve])]
        assert joins and f[2] <= max(w[2] for w in joins)
        if f[4]["ahead"]:
            (step,) = [s for s in steps if _inside(serve, [s])]
            assert step[1] <= f[1]
        else:
            assert serve[1] <= f[1]


def test_step_phases_nest_inside_decode_step_inside_tick(served):
    _, _, _, events = served
    ticks, steps = _named(events, "tick"), _named(events, "decode_step")
    serves = _named(events, "serve")
    assert all(_inside(s, ticks) for s in steps)
    for name in ("router_sync", "serve", "wave"):
        assert all(_inside(e, steps) for e in _named(events, name)), name
    # a load on the decode thread ships inside the layer that needs it
    # (the loader threads' fetches: test_fetches_run_between_...)
    decode = _decode_threads(events)
    assert all(_inside(e, serves) for e in _named(events, "expert_load")
               if e[3] in decode)
    for name in ("admit", "peek", "kv_gather", "commit", "model_clock"):
        assert all(_inside(e, ticks) for e in _named(events, name)), name


def test_tracing_changes_no_token(served):
    plain, traced, _, _ = served
    assert plain.outputs.keys() == traced.outputs.keys()
    for rid in plain.outputs:
        np.testing.assert_array_equal(plain.outputs[rid],
                                      traced.outputs[rid])
