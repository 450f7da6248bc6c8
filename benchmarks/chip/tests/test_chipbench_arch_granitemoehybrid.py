"""The Granite-4.0-H plug-in (``arch/granitemoehybrid.py``) against the
program at a small size on the CPU: one period of ten layers (Mamba-2
mixers and one NoPE attention layer, a routed FFN with a shared expert
on each), the four Granite multipliers, routed experts handed to the
engine as host ``numpy`` leaves and served through ``ServingLoop`` ->
``ODMoEEngine``."""
from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401
from chipbench import config, spec, yardstick

TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
RAW = {
    "name": "tiny-hybrid", "source": "a small model of the Granite-4.0-H shape",
    "model_type": "granitemoehybrid", "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
    "layer_types": TYPES, "logits_scaling": 16, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 4, "mamba_proj_bias": False,
    "normalization_function": "rmsnorm", "num_attention_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 10,
    "num_key_value_heads": 2, "num_local_experts": 8,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "shared_intermediate_size": 48, "tie_word_embeddings": True,
    "vocab_size": 512, "torch_dtype": "float32",
    "program": {"n_workers": 3, "predictor": "sep", "shadow_scheme": "int8"},
}
# attention_multiplier: 1 / head_dim, the rule the published 1/128 follows
# (head dim 128); 16 here
# (prompt length, new tokens): 17 decode steps each
REQUESTS = [(13, 18), (7, 18)]
# float32 on both sides: the program's chunked Mamba-2 scan, its
# per-layer programs and grouped expert GEMM sum in other orders than
# the reference's sequential scan and dense experts (about 1e-7 here)
F32_TOL = 1e-5


def _serve(raw, params, max_batch):
    from repro.core import ODMoEEngine
    from repro.serve import Request, ServingLoop
    bc = config.from_dict(raw)
    eng = ODMoEEngine(bc.model, params, n_workers=bc.n_workers,
                      predictor="sep", shadow_scheme="int8",
                      keep_logits=True)
    loop = ServingLoop(eng, max_batch=max_batch)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, raw["vocab_size"], n
                                               ).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(REQUESTS)]
    return eng, loop, loop.run(reqs)


def _served(res, rid):
    st = res.states[rid]
    return yardstick.Served(
        prompt=np.asarray(st.request.prompt, np.int32),
        tokens=np.asarray(st.generated, np.int32),
        routing=[{lr.layer: np.asarray(lr.true[0]) for lr in rec.layers}
                 for rec in st.trace.records],
        logits=[np.asarray(l, np.float32)[0] for l in st.trace.logits])


@pytest.fixture(scope="module")
def f32():
    """One composed serve (batch 2) and one request at a time, float32;
    the engine's device holdings read right after the composed serve."""
    bc = config.from_dict(RAW)
    params = bc.plugin.make_params(bc.model, 5)
    eng, loop, composed = _serve(RAW, params, max_batch=2)
    gc.collect()
    d, f, e = bc.model.d_model, bc.model.d_expert_resolved, \
        bc.model.num_experts
    stacked = [a.shape for a in jax.live_arrays() if a.ndim >= 3
               and a.dtype.kind == "f" and tuple(a.shape[-3:])
               in ((e, d, f), (e, f, d))]
    held = dict(device=eng.memory_report()["device"], stacked=stacked,
                expert_bytes=eng.store.expert_bytes)
    del eng, loop
    _, _, solo = _serve(RAW, params, max_batch=1)
    return bc, params, composed, solo, held


def test_configuration_maps_the_published_keys():
    bc = config.load("granite4-h-small-10L")
    cfg = bc.model
    assert bc.plugin is spec.arch_module("granitemoehybrid")
    assert [m for m, _ in cfg.layer_kinds()] == \
        ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert all(ff == "moe" for _, ff in cfg.layer_kinds())
    assert (cfg.d_model, cfg.num_experts, cfg.top_k, cfg.d_expert_resolved,
            cfg.d_shared, cfg.vocab_size) == (4096, 72, 10, 768, 1536,
                                              100352)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.d_inner) == (128, 64, 128, 4, 8192)
    assert cfg.rope_fraction == 0.0                       # NoPE
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.0078125, 0.22, 16.0)
    # one period of the published 40 layers: 720 routed experts, 13.6 GB
    routed = cfg.num_layers * cfg.num_experts * 3 * 4096 * 768 * 2
    assert routed == pytest.approx(13.6e9, rel=0.01)


def test_layer_types_must_recur():
    plugin = spec.arch_module("granitemoehybrid")
    bad = dict(RAW, layer_types=["attention"] + ["mamba"] * 4
               + ["attention"] * 2 + ["mamba"] * 3)
    with pytest.raises(ValueError, match="one interval"):
        plugin.model_config(bad)


def test_routed_experts_reach_the_engine_on_the_host(f32):
    bc, params, _, _, _ = f32
    for sub in params["layers"]:
        for name in ("w_gate", "w_up", "w_down"):
            assert isinstance(sub["ff"][name], np.ndarray)
        assert isinstance(sub["ff"]["router"], jax.Array)


def test_serving_matches_the_reference(f32):
    """Prefill, then 17 decode steps through the serving loop, against
    the float32 reference forced on the served routing."""
    bc, params, composed, _, _ = f32
    arch = bc.plugin.Arch.from_config(RAW)
    for rid in composed.states:
        s = _served(composed, rid)
        assert s.steps >= 16
        r = yardstick.summarize([yardstick.program_readings(
            bc.plugin, arch, params, s)])
        assert r["token_gap"] == 0.0
        assert r["logit_err_max"] < F32_TOL
        assert r["route_gap_max"] == 0.0


def test_composed_batch_matches_one_at_a_time(f32):
    """Mamba-2 state joined into a batch of 2 and sliced back gives each
    request the logits it gets alone."""
    _, _, composed, solo, _ = f32
    assert max(len(s.request_ids) for s in composed.steps) == 2
    for rid in composed.states:
        np.testing.assert_array_equal(composed.outputs[rid],
                                      solo.outputs[rid])
        a = np.stack([np.asarray(l) for l in
                      composed.states[rid].trace.logits])
        b = np.stack([np.asarray(l) for l in solo.states[rid].trace.logits])
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_no_routed_expert_array_on_the_device(f32):
    bc, _, _, _, held = f32
    dev = held["device"]
    assert dev["routed_experts"] == 0
    assert dev["worker_slots"] == 0            # evicted after each layer
    assert held["stacked"] == []               # no (E, d, f) array lives
    # prefill streamed at most one layer's experts
    cfg = bc.model
    assert 0 < dev["prefill_stream_peak"] <= \
        cfg.num_experts * held["expert_bytes"]
    assert dev["non_expert"] > 0 and dev["shadow"] > 0


PIECES = {
    "shared_expert": {"d_shared": 0},
    "nope": {"nope": False},
    "embedding_multiplier": {"embedding_multiplier": 1.0},
    "attention_multiplier": {"attention_multiplier": 0.0},
    "residual_multiplier": {"residual_multiplier": 1.0},
    "logits_scaling": {"logits_scaling": 1.0},
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_leaving_a_piece_out_fails(f32, piece):
    """The reference without one piece of the architecture is far from
    what the program served: the program computes each piece."""
    bc, params, composed, _, _ = f32
    arch = dataclasses.replace(bc.plugin.Arch.from_config(RAW),
                               **PIECES[piece])
    s = _served(composed, 0)
    r = yardstick.summarize([yardstick.program_readings(
        bc.plugin, arch, params, s)])
    assert r["logit_err"] > 10 * F32_TOL


def test_float8_control_fails_where_bfloat16_passes():
    """In bfloat16 the program reads inside the tiny cell's limits and
    the float8 control does not."""
    raw = dict(RAW, torch_dtype="bfloat16")
    bc = config.from_dict(raw)
    params = bc.plugin.make_params(bc.model, 7)
    _, _, res = _serve(raw, params, max_batch=1)
    arch = bc.plugin.Arch.from_config(raw)
    served = [_served(res, rid) for rid in res.states]
    prog = yardstick.summarize([yardstick.program_readings(
        bc.plugin, arch, params, s) for s in served])
    ctl = yardstick.summarize([yardstick.control_readings(
        bc.plugin, arch, params, s) for s in served])
    limits = chipbench_tiny.LIMITS
    assert yardstick.judge(prog, limits)[0]
    assert not yardstick.judge(ctl, limits)[0]


def test_operation_counts():
    cfg = config.load("granite4-h-small-10L").model
    plugin = spec.arch_module("granitemoehybrid")
    # one prompt token is one decoded token at context 1
    assert plugin.prompt_flops(cfg, 1) == plugin.decode_flops(cfg, 1)
    # one attention layer: 4 x heads x head_dim per key of context
    assert plugin.decode_flops(cfg, 101) - plugin.decode_flops(cfg, 1) \
        == 100 * 4 * 32 * 128
    d, di, ns, nh, f = 4096, 8192, 128, 128, 768
    mamba = 2 * d * (2 * di + 2 * ns + nh) + 2 * di * d \
        + 2 * 4 * (di + 2 * ns) + 5 * nh * 64 * ns
    attn = 2 * d * (4096 + 2 * 1024) + 2 * 4096 * d
    ffn = 2 * d * 72 + 10 * 6 * d * f + 6 * d * 1536
    assert plugin.active_flops(cfg) == \
        9 * mamba + attn + 10 * ffn + 2 * d * 100352


def test_mamba_step_bytes_are_the_layer_programs_weights(f32):
    """At zero rows, the bytes of a Mamba-2 layer's decode program are
    the weights it is handed: its stack without the routed experts."""
    bc, params, _, _, _ = f32
    cfg = bc.model
    sub = params["layers"][0]
    weights = sum(a.nbytes for k, v in sub.items()
                  for a in jax.tree.leaves(v if k != "ff" else {
                      n: w for n, w in v.items()
                      if n not in ("w_gate", "w_up", "w_down")}))
    assert bc.plugin.mamba_step_bytes(cfg, 0) == weights
    per_row = bc.plugin.mamba_step_bytes(cfg, 1) - weights
    state = 4 * 32 * 16 * 4 + 3 * (128 + 32) * 4
    assert per_row == 2 * state + 4 * 64 * 4


# ------------------------------------------------------------- readers
def _reader(name):
    return spec.metric_reader(name)


def _mamba_run(programs, rows, cfg, plugin):
    from types import SimpleNamespace as NS
    from chipbench import trace as T
    return NS(trace=NS(window=(0.0, 10_000.0),
                       devices=[T.DeviceTrace(ops=[], programs=programs)]),
              peaks=NS(hbm_bytes_s=1e9), plugin=plugin, cfg=cfg,
              records=[NS(layers=[NS(true=np.zeros((b, 3)))])
                       for b in rows])


def test_mamba_roofline_reads_the_named_programs():
    from chipbench import trace as T
    bc = config.from_dict(RAW)
    cfg, plugin = bc.model, bc.plugin
    n_mamba = sum(m == "mamba" for m, _ in cfg.layer_kinds())
    # two decode steps of one and two rows: a program per Mamba-2 layer,
    # 100 ns each, plus programs the reader must leave alone
    progs = [T.Ev("jit_mamba_layer_step(7)", 200.0 * i, 100.0)
             for i in range(2 * n_mamba)]
    progs += [T.Ev("jit_attn_layer_step(8)", 9000.0, 500.0),
              T.Ev("jit_mamba_layer_step(7)", 9900.0, 500.0)]  # past the end
    run = _mamba_run(progs, [1, 2], cfg, plugin)
    need = n_mamba * (plugin.mamba_step_bytes(cfg, 1)
                      + plugin.mamba_step_bytes(cfg, 2))
    got = _reader("mamba_roofline.decode")(run)
    assert got == pytest.approx(100.0 * need / 1e9 / (2 * n_mamba * 1e-7))


def test_mamba_roofline_is_silent_without_the_programs():
    from chipbench import trace as T
    bc = config.from_dict(RAW)
    run = _mamba_run([T.Ev("jit_attn_layer_step(8)", 0.0, 50.0)], [1],
                     bc.model, bc.plugin)
    assert _reader("mamba_roofline.decode")(run) is None
    granite = config.load("granite3-3b-a800m-8L")
    run = _mamba_run([T.Ev("jit_mamba_layer_step(7)", 0.0, 50.0)], [1],
                     granite.model, granite.plugin)
    assert _reader("mamba_roofline.decode")(run) is None


def test_prefill_stream_reader(monkeypatch):
    from types import SimpleNamespace as NS
    from chipbench import program_spans as P
    spans = [P.Span("odmoe.tick", 0, 1000, (1, 0)),
             P.Span("odmoe.prefill_stream", 100, 300, (1, 0),
                    {"layer": 0, "experts": 8, "nbytes": 9}),
             P.Span("odmoe.prefill_stream", 900, 300, (1, 0),
                    {"layer": 1, "experts": 8, "nbytes": 9})]
    run = NS(trace=NS(window=(0.0, 1000.0)),
             counters={"decoded_tokens": 4})
    monkeypatch.setattr(P, "window_spans", lambda r: spans)
    # 300 ns + the 100 ns of the second span inside the window, 4 tokens
    assert _reader("prefill_stream_ms_per_token")(run) == \
        pytest.approx(400e-6 / 4)
    monkeypatch.setattr(P, "window_spans", lambda r: spans[:1])
    assert _reader("prefill_stream_ms_per_token")(run) is None
