"""Routed experts held only in host memory, the shadow held as its
quantization codes, and what they bring on the serving path: a hybrid
(Mamba-2 + NoPE attention) decoder with a shared expert, its streamed
prefill and its spans, and the memory report's split of device bytes."""
import gc
import glob
import os

import jax
import numpy as np
import pytest

from conftest import tiny_hybrid, tiny_moe
from repro.core import ODMoEEngine, SEPShadow
from repro.models import greedy_generate, init_params
from repro.quant import shadow_params
from repro.serve import Request, ServingLoop

ROUTED = ("w_gate", "w_up", "w_down")


def host_experts(params):
    """``params`` with every routed expert weight a host ``numpy`` array."""
    layers = []
    for sub in params["layers"]:
        ff = dict(sub["ff"])
        ff.update({n: np.asarray(ff[n]) for n in ROUTED})
        layers.append(dict(sub, ff=ff))
    return dict(params, layers=tuple(layers))


@pytest.fixture(scope="module")
def hybrid():
    cfg = tiny_hybrid()
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def _prompt(cfg, n=9, seed=1):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, cfg.vocab_size, (1, n)).astype(
        np.int32)}


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_hybrid_engine_matches_greedy_generate(hybrid, host):
    """The engine's per-layer decode (mixer, shared expert, multipliers)
    and, with host-held experts, its streamed prefill give the tokens of
    the model's own greedy decode."""
    cfg, params = hybrid
    batch = _prompt(cfg)
    ref = np.asarray(greedy_generate(cfg, params, batch, 8))
    given = host_experts(params) if host else params
    eng = ODMoEEngine(cfg, given, n_workers=3, predictor="sep",
                      shadow_scheme="int8")
    assert eng.host_experts is host
    toks, trace = eng.generate(batch, 8)
    np.testing.assert_array_equal(np.asarray(toks), ref)
    assert all(len(r.layers) == cfg.num_layers for r in trace.records)


@pytest.mark.parametrize("arch", ["moe", "hybrid"])
def test_code_shadow_predicts_the_simulated_shadows_routing(arch):
    """The shadow holding int8 codes (dequantizing only the experts it
    routes to) predicts, step for step, the routing and tokens of the
    same shadow computed on simulated (dequantized) weights."""
    cfg = tiny_moe() if arch == "moe" else tiny_hybrid()
    params = init_params(cfg, jax.random.PRNGKey(4))
    codes = SEPShadow(cfg, params, "int8")
    sim = SEPShadow(cfg, params, "int8")
    sim.params = shadow_params(params, "int8")
    batch = _prompt(cfg, 11, seed=2)
    states = [s.prefill_state(batch, 24) for s in (codes, sim)]
    np.testing.assert_array_equal(np.asarray(states[0]["token"]),
                                  np.asarray(states[1]["token"]))
    for _ in range(6):
        (pc, states[0]), (ps, states[1]) = [
            s.step_state(st, st["token"]) for s, st in zip((codes, sim),
                                                          states)]
        assert pc.keys() == ps.keys()
        for li in pc:
            np.testing.assert_array_equal(pc[li], ps[li])
        np.testing.assert_array_equal(np.asarray(states[0]["token"]),
                                      np.asarray(states[1]["token"]))


def test_code_shadow_is_smaller_than_the_model(hybrid):
    cfg, params = hybrid
    sh = SEPShadow(cfg, params, "int8")
    held = sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree.leaves(sh.params))
    full = sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree.leaves(params))
    assert held < 0.5 * full                 # codes + scales of f32


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_memory_report_splits_device_bytes(hybrid, host):
    cfg, params = hybrid
    given = host_experts(params) if host else params
    eng = ODMoEEngine(cfg, given, n_workers=3, predictor="sep",
                      shadow_scheme="int8")
    loop = ServingLoop(eng, max_batch=2)
    loop.start([], cache_len=40)
    loop.add_request(Request(rid=0, prompt=_prompt(cfg)["tokens"][0],
                             max_new_tokens=4))
    loop.tick()
    live = loop.memory_report()["device"]
    routed = sum(params["layers"][p]["ff"][n].nbytes
                 for p in range(len(params["layers"])) for n in ROUTED)
    assert live["routed_experts"] == (0 if host else routed)
    assert live["caches"] > 0                  # the request's KV and SSM
    assert live["non_expert"] > 0 and live["shadow"] > 0
    assert live["worker_slots"] == 0           # evicted after each layer
    assert (live["prefill_stream_peak"] > 0) is host
    while loop.tick():
        pass
    assert loop.memory_report()["device"]["caches"] == 0


def test_host_experts_ship_at_their_own_precision(hybrid):
    cfg, params = hybrid
    with pytest.raises(ValueError, match="host-held experts"):
        ODMoEEngine(cfg, host_experts(params), n_workers=3,
                    transport="int8")


def test_mamba_layer_program_is_named_apart(hybrid):
    """The device trace tells a Mamba-2 layer's decode program from the
    attention layer's by name."""
    from repro.core.engine import _mixer_router_step
    from repro.models.transformer import init_caches
    cfg, params = hybrid
    eng = ODMoEEngine(cfg, params, n_workers=3, predictor="none")
    caches = init_caches(cfg, 1, 16, np.float32)
    x = np.zeros((1, 1, cfg.d_model), np.float32)
    pos = np.zeros((1,), np.int32)
    names = {}
    for li in (0, 5):
        kinds = cfg.layer_kinds()[li]
        stack, r = eng._at[li]
        cache = jax.tree.map(lambda a: a[0], caches[li])
        names[kinds[0]] = _mixer_router_step(cfg, kinds).lower(
            stack, r, x, cache, pos).as_text().split("\n")[0]
    assert "jit_mamba_layer_step" in names["mamba"]
    assert "jit_attn_layer_step" in names["attn"]


def test_prefill_stream_spans(hybrid, tmp_path):
    """One ``odmoe.prefill_stream`` span per routed layer of each
    streamed prefill, inside its ``odmoe.prefill``, with the layer, the
    experts the prompt routed to and the bytes streamed."""
    from jax.profiler import ProfileData
    cfg, params = hybrid
    eng = ODMoEEngine(cfg, host_experts(params), n_workers=3,
                      predictor="sep", shadow_scheme="int8")
    reqs = [Request(rid=i, prompt=_prompt(cfg, n, seed=i)["tokens"][0],
                    max_new_tokens=3) for i, n in enumerate((9, 5))]
    ServingLoop(eng, max_batch=2).run(reqs)            # warm
    eng = ODMoEEngine(cfg, host_experts(params), n_workers=3,
                      predictor="sep", shadow_scheme="int8")
    jax.profiler.start_trace(str(tmp_path))
    try:
        ServingLoop(eng, max_batch=2).run(
            [Request(rid=r.rid + 10, prompt=r.prompt, max_new_tokens=3)
             for r in reqs])
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (f,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                  "*", "*.xplane.pb"))
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
          for plane in ProfileData.from_file(f).planes
          for line in plane.lines for e in line.events
          if e.name in ("odmoe.prefill_stream", "odmoe.prefill")]
    streams = [e for e in ev if e[0] == "odmoe.prefill_stream"]
    prefills = [e for e in ev if e[0] == "odmoe.prefill"]
    assert len(prefills) == 2
    assert len(streams) == 2 * cfg.num_layers
    assert all(any(p[1] <= s[1] and s[2] <= p[2] for p in prefills)
               for s in streams)
    assert sorted(s[3]["layer"] for s in streams) == \
        sorted(2 * list(range(cfg.num_layers)))
    block = 8 * eng.store.expert_bytes                 # one block of 8
    for s in streams:
        assert 1 <= s[3]["experts"] <= cfg.num_experts
        assert s[3]["nbytes"] == block
    assert eng.prefill_stream_peak == block
