"""The jit-grouped expert-FFN hot path: bit-exactness against the
retired per-(row, rank) loop, fleet-batched shadow peek dispatch
accounting, and the exact shadow-footprint report.

The load-bearing contract: ``grouped_topk_contrib`` + ``combine_topk``
(repro.kernels.moe_gemm) produce, for every (row, rank) pair, the SAME
bits the retired Python loop produced — whatever the batch size, top-k,
wave partition, or transport precision — because each row of each
expert's GEMM is its own dot product and the rank-order reduction tree
is fixed.  The engine (wave compute from worker slots), the reference
``greedy_generate`` (``moe_method="grouped"``) and the SEP shadow all
consume these two functions, so engine ≡ reference needs no
loop-order coincidences.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import tiny_moe
from repro.core import AlignmentPolicy, ODMoEEngine
from repro.kernels.moe_gemm import combine_topk, grouped_topk_contrib
from repro.models import greedy_generate, init_params
from repro.models.moe import init_moe, moe_dense, moe_grouped


# --------------------------------------------- primitive vs retired loop
def _retired_loop(h, weights, true, gates):
    """The pre-refactor arithmetic, verbatim: per-(row, rank) vector
    matmuls accumulated in rank order (engine._compute_wave_loop)."""
    y = jnp.zeros((true.shape[0], h.shape[1]), jnp.float32)
    for bi in range(true.shape[0]):
        hb = h[bi].astype(jnp.float32)
        for j in range(true.shape[1]):
            wd = weights[int(true[bi, j])]
            out = (jax.nn.silu(hb @ wd["w_gate"]) * (hb @ wd["w_up"])
                   ) @ wd["w_down"]
            y = y.at[bi].add(float(gates[bi, j]) * out)
    return np.asarray(y)


@settings(deadline=None, max_examples=15)
@given(seed=st.integers(0, 10**9), b=st.integers(1, 6),
       k=st.integers(1, 4), n_waves=st.integers(1, 3))
def test_grouped_contrib_bitexact_vs_retired_loop(seed, b, k, n_waves):
    """Random batch sizes, top-k widths and wave partitions: the
    grouped path reproduces the retired loop BIT-identically, including
    multi-wave overflow (experts split across several grouped calls
    accumulating into one (B, k, d) buffer)."""
    rng = np.random.default_rng(seed)
    e, d, f = int(rng.integers(k, 9)), 16, 24
    weights = [
        {"w_gate": jnp.asarray(rng.normal(size=(d, f)).astype(np.float32)),
         "w_up": jnp.asarray(rng.normal(size=(d, f)).astype(np.float32)),
         "w_down": jnp.asarray(rng.normal(size=(f, d)).astype(np.float32))}
        for _ in range(e)]
    h = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    # routed experts: distinct per row (as jax.lax.top_k guarantees)
    true = np.stack([rng.choice(e, size=min(k, e), replace=False)
                     for _ in range(b)]).astype(np.int32)
    g = rng.random((b, true.shape[1])).astype(np.float32) + 0.1
    gates = g / g.sum(axis=1, keepdims=True)
    # split the routed experts across waves (engine overflow behaviour)
    routed = sorted({int(x) for x in true.reshape(-1)})
    waves = [routed[i::n_waves] for i in range(n_waves)]
    contrib = None
    for wave in waves:
        if not wave:
            continue
        eid = np.asarray(wave)
        match = true[..., None] == eid
        slot = np.where(match.any(-1), match.argmax(-1), -1).astype(np.int32)
        wc = grouped_topk_contrib(
            h, jnp.stack([weights[x]["w_gate"] for x in wave]),
            jnp.stack([weights[x]["w_up"] for x in wave]),
            jnp.stack([weights[x]["w_down"] for x in wave]),
            jnp.asarray(slot), jnp.asarray(gates))
        contrib = wc if contrib is None else contrib + wc
    got = np.asarray(combine_topk(contrib))
    want = _retired_loop(h, weights, true, gates)
    assert np.array_equal(got, want), (b, k, e, n_waves)


# ------------------------------------------------ engine: grouped ≡ loop
# int8/nf4 ride the slow tier (transport packing at engine construction
# dominates); fp32 keeps a fast-tier end-to-end pin
@pytest.mark.parametrize("transport", [
    None,
    pytest.param("int8", marks=pytest.mark.slow),
    pytest.param("nf4", marks=pytest.mark.slow)])
def test_engine_grouped_bitexact_vs_loop_engine(transport):
    """End to end, under forced multi-wave overflow (4 workers, batch 3,
    top-2 -> up to 6 unique experts) and under mixed-precision
    transport: the production grouped engine emits tokens bit-identical
    to the retired loop engine AND to ``greedy_generate`` under the
    same policy."""
    cfg = tiny_moe(num_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (3, 7),
                                          0, cfg.vocab_size)}
    toks = {}
    for mode in ("grouped", "loop"):
        eng = ODMoEEngine(cfg, params, n_workers=4, predictor="none",
                          physical_loading=False, transport=transport,
                          wave_compute=mode)
        out, trace = eng.generate(batch, 4, AlignmentPolicy(1, 1))
        toks[mode] = np.asarray(out)
        if mode == "grouped":   # overflow genuinely exercised waves
            assert any(len(lr.waves) > 1 for tr in trace.records
                       for lr in tr.layers)
    assert np.array_equal(toks["grouped"], toks["loop"])
    ref = np.asarray(greedy_generate(cfg, params, batch, 4,
                                     transport=transport))
    assert np.array_equal(toks["grouped"], ref)


def test_moe_grouped_matches_dense_dispatch(key):
    """The reference ``grouped`` dispatch routes identically to the
    dense oracle and its output matches to accumulation-order
    tolerance (dense sums all E experts in index order; grouped sums
    the routed k in rank order)."""
    cfg = tiny_moe()
    params = init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (9, cfg.d_model))
    ref, aux_ref = moe_dense(cfg, params, x)
    out, aux = moe_grouped(cfg, params, x)
    np.testing.assert_array_equal(np.asarray(aux_ref["topk_idx"]),
                                  np.asarray(aux["topk_idx"]))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=1e-5, rtol=1e-5)
    assert "load_balance_loss" in aux


# ------------------------------------------- fleet-batched shadow peeks
@pytest.mark.slow
def test_one_shadow_dispatch_per_serving_step():
    """Pinned dispatch accounting for the fleet-batched peek: one
    composed shadow step per serving iteration, however many requests
    ride — where the per-request loop dispatched one step per request
    per iteration — with every token stream still bit-identical to its
    solo reference."""
    from repro.core.predictor import SEPShadow
    from repro.serve import Request, ServingLoop

    cfg = tiny_moe(num_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=4, arrival_s=0.0) for i in range(3)]
    eng = ODMoEEngine(cfg, params, n_workers=8, predictor="sep",
                      shadow_scheme="int8", physical_loading=False)
    calls = {"n": 0, "rows": 0}
    orig = SEPShadow.step_state

    def counting(self, state, token):
        calls["n"] += 1
        calls["rows"] += int(token.shape[0])
        return orig(self, state, token)

    SEPShadow.step_state = counting
    try:
        res = ServingLoop(eng, max_batch=3).run(reqs)
    finally:
        SEPShadow.step_state = orig
    for r in reqs:
        ref = np.asarray(greedy_generate(
            cfg, params, {"tokens": jnp.asarray(r.prompt)[None, :]},
            r.max_new_tokens))[0]
        assert np.array_equal(ref, res.outputs[r.rid]), r.rid
    # the pin: exactly one composed dispatch per serving iteration ...
    assert calls["n"] == len(res.steps)
    # ... batching multiple requests' shadows into it (the retired
    # per-request path would have dispatched once per row)
    assert res.mean_batch > 1.0
    assert calls["rows"] > calls["n"]


# ------------------------------------------------- shadow memory report
@pytest.mark.parametrize("scheme", ["fp16", "int8", "nf4"])
def test_shadow_node_bytes_match_real_packed_sizes(scheme):
    """``memory_report()['shadow_node_bytes']`` equals the byte-exact
    footprint of the shadow tree: per quantized leaf, the REAL packed
    payload (``TransportCodec.pack(...).nbytes`` — codes + scales); per
    full-precision leaf (norms, small vectors), its real ``nbytes``.
    The retired fraction table got this wrong whenever a leaf skipped
    quantization."""
    from repro.quant import get_codec, shadow_params
    from repro.quant.quantize import _MIN_QUANT_SIZE

    cfg = tiny_moe(num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ODMoEEngine(cfg, params, predictor="sep", shadow_scheme=scheme,
                      physical_loading=False)
    codec = get_codec(scheme)
    expect = skipped = 0
    # the shadow holds codes; its deployed footprint is that of the
    # quantized tree they stand for, leaf by leaf
    for w in jax.tree.leaves(shadow_params(params, scheme)):
        if w.ndim >= 2 and w.size >= _MIN_QUANT_SIZE and jnp.issubdtype(
                w.dtype, jnp.floating):
            expect += codec.pack(w).nbytes          # real packed bytes
        else:
            expect += w.size * w.dtype.itemsize
            skipped += w.size * w.dtype.itemsize
    rep = eng.memory_report()
    assert rep["shadow_node_bytes"] == expect
    assert skipped > 0                  # some leaves really stay fp32
    # the old flat-fraction estimate cannot reproduce the exact figure
    factor = {"fp16": 0.5, "int8": 0.25, "nf4": 0.125}[scheme]
    naive = int(rep["fully_cached_bytes"] * factor)
    assert rep["shadow_node_bytes"] != naive
