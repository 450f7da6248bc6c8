"""Serving throughput — continuous batching over the cacheless engine.

Drives REAL engine serving runs (prefill-on-admission, SEP peeks,
composed decode) through ``ServingLoop`` on the shared bench model and
reports, per traffic point:

  * aggregate throughput (tok/s of modeled edge time) and makespan,
  * TTFT / TPOT mean and p50/p95/p99 across requests,
  * mean composed batch size and load amortization (requests served per
    physical expert load — the multi-request demand-aggregation win),
  * ``overlap`` vs ``fifo`` composition at the same traffic,
  * a trace-driven MULTI-TENANT point (``repro.serve.workload``:
    heavy-tailed lengths, bursty arrivals, interactive+batch tenant
    classes) under the full SLO-aware stack — priority admission,
    deadline-slack preemption, per-tenant fair composition over a
    constrained KV pool — with per-class p95s and SLO attainment.

``--smoke`` (the CI fast job) gates three things cheaply: the
multi-tenant trace run completes with every request's tokens
bit-identical to its solo ``greedy_generate`` and every report field
finite; and queue admission/retire bookkeeping scales ~O(log n) per op
(a pure-bookkeeping run at 2k vs 8k synthetic requests must grow
~linearly — the old ``list.pop(0)`` / ``active.remove`` quadratic
blowup fails the gate).

The BENCH json artifact (benchmarks/artifacts/serving_throughput.json)
holds the full per-point report for the docs and CI trend checks.
"""
from __future__ import annotations

import math
import time

import numpy as np

from repro.core import ODMoEEngine
from repro.serve import (BatchComposer, KVPool, Request, RequestQueue,
                         RequestState, ServingLoop, WorkloadSpec,
                         make_trace, make_traffic)

from .common import bench_model, record_bench, row, save_artifact, timed

# (label, arrival rate req/s of modeled time, composition policy,
#  async: threaded prefetch executor + LRU residency)
POINTS = [
    ("burst/overlap", 0.0, "overlap", False),
    ("burst/fifo", 0.0, "fifo", False),
    ("burst/overlap-async", 0.0, "overlap", True),
    ("r200/overlap", 200.0, "overlap", False),
    ("r20/overlap", 20.0, "overlap", False),
]


def serve_point(cfg, params, rate: float, policy: str, n: int,
                tokens: int, max_batch: int = 4,
                use_async: bool = False) -> dict:
    from repro.fleet import uniform_profiles
    eng = ODMoEEngine(cfg, params, n_workers=8, predictor="sep",
                      shadow_scheme="int8",
                      # capacity-2 workers give released residents a slot
                      # to survive in; the modeled clock then prices only
                      # the experts that physically shipped (lr.shipped)
                      profiles=(uniform_profiles(8, capacity=2)
                                if use_async else None),
                      prefetch="thread" if use_async else "sync",
                      residency="lru" if use_async else None,
                      peek_horizon=0)
    loop = ServingLoop(eng, max_batch=max_batch,
                       composer=BatchComposer(max_batch, policy))
    res = loop.run(make_traffic(cfg, n, rate, max_new=tokens))
    eng.close()
    rep = res.timings.report()
    served = [len(e.requests) for e in eng.slots.events if e.requests]
    rep.update({
        "arrival_rate": rate,
        "compose": policy,
        "mean_batch": res.mean_batch,
        "loads": len(eng.slots.events),
        "requests_per_load": float(np.mean(served)) if served else 0.0,
        "loads_per_token": (len(eng.slots.events)
                            / max(rep["total_tokens"], 1)),
        "bytes_moved": eng.slots.bytes_moved,
    })
    if use_async:
        ps = res.prefetch_stats
        rep["rehit_rate"] = ps["rehit_rate"]
        fetched = (ps.get("prefetch_prefetched", 0)
                   + ps.get("prefetch_inline", 0)
                   + ps.get("prefetch_demand_fetches", 0))
        rep["overlap_efficiency"] = (ps.get("prefetch_prefetched", 0)
                                     / fetched if fetched else 0.0)
    return rep


# ------------------------------------------- trace-driven multi-tenant
def serve_trace_point(cfg, params, n: int, tokens: int,
                      max_batch: int = 4, verify: bool = False) -> dict:
    """One run of the full SLO-aware stack on a trace-driven workload:
    heavy-tailed lengths, bursty arrivals, interactive (weight 4, real
    SLOs) + batch (best-effort) tenants, priority admission,
    deadline-slack preemption and fair composition over a KV pool at
    ~60% of the dense footprint (so deferral/preemption actually
    fire).  ``verify`` additionally checks every request's tokens
    against its solo ``greedy_generate`` run."""
    spec = WorkloadSpec(n_requests=n, rate=150.0, arrival="bursty",
                        prompt_median=10, min_prompt=4, max_prompt=24,
                        output_median=max(tokens // 2, 2),
                        max_output=tokens)
    reqs = make_trace(cfg, spec, seed=0)
    cache_len = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 2
    page_tokens = 4
    window_pages = -(-cache_len // page_tokens)
    num_pages = max(window_pages + 1,
                    int(window_pages * len(reqs) * 0.6))
    pool = KVPool(cfg, num_pages=num_pages, page_tokens=page_tokens)
    eng = ODMoEEngine(cfg, params, n_workers=8, predictor="sep",
                      shadow_scheme="int8")
    loop = ServingLoop(eng, max_batch=max_batch,
                       composer=BatchComposer(max_batch, "fair",
                                              kv_pool=pool),
                       kv_pool=pool, preempt="slack", admit="priority")
    res = loop.run(reqs)
    if verify:
        import jax.numpy as jnp
        from repro.models import greedy_generate
        for r in reqs:
            ref = np.asarray(greedy_generate(
                cfg, params,
                {"tokens": jnp.asarray(r.prompt)[None, :]},
                r.max_new_tokens))[0]
            assert np.array_equal(ref, res.outputs[r.rid]), \
                f"request {r.rid} diverged from its solo reference"
    rep = res.timings.report()
    rep.update(arrival="bursty", preempt="slack", admit="priority",
               compose="fair", mean_batch=res.mean_batch,
               deferred=res.kv_stats["deferred_admissions"],
               preemptions=res.kv_stats["preemptions"],
               per_tenant=res.tenant_report())
    _assert_finite_report(rep)
    return rep


def _assert_finite_report(rep: dict, path: str = "") -> None:
    """Every numeric field JSON-safe: no NaN, no inf — the empty-run /
    zero-makespan regression gate."""
    for k, v in rep.items():
        if isinstance(v, dict):
            _assert_finite_report(v, f"{path}{k}.")
        elif isinstance(v, float):
            assert math.isfinite(v), f"non-finite metric {path}{k}={v}"


# ------------------------------------------------ queue-scaling smoke
def queue_ops_seconds(n: int) -> float:
    """Pure bookkeeping at trace scale, no engine: admit ``n`` synthetic
    requests through ``RequestQueue`` in arrival slices and retire the
    active population in interleaved halves.  Total work is ~O(n log n)
    with the heap/dict queue; the old sorted-list/``list.remove``
    bookkeeping made this quadratic."""
    reqs = [Request(rid=i, prompt=np.zeros(4, np.int32),
                    max_new_tokens=1, arrival_s=i * 1e-3)
            for i in range(n)]
    q = RequestQueue(reqs)
    t0 = time.perf_counter()
    now, seq = 0.0, 0
    slice_s = max(n // 32, 1) * 1e-3
    while not q.all_done:
        now += slice_s
        for r in q.pop_arrived(now):
            s = RequestState(request=r, token=None, cache_list=[],
                             pos=None)
            s.admit_seq = seq
            seq += 1
            q.activate(s)
        act = q.active
        for s in act[:max(len(act) // 2, 1)]:
            q.retire(s)
    return time.perf_counter() - t0


def queue_scaling_gate(n_small: int = 2000, factor: int = 4,
                       max_ratio: float = 10.0) -> dict:
    """Admission/retire must scale ~O(log n) per op: growing the trace
    ``factor``x may grow total bookkeeping time by at most
    ``max_ratio``x (best of 3 — a quadratic queue lands around
    ``factor**2``x)."""
    t_small = min(queue_ops_seconds(n_small) for _ in range(3))
    t_big = min(queue_ops_seconds(n_small * factor) for _ in range(3))
    ratio = t_big / max(t_small, 1e-9)
    assert ratio < max_ratio, (
        f"queue bookkeeping scaled {ratio:.1f}x for {factor}x requests "
        f"(quadratic?)")
    return {"n_small": n_small, "n_big": n_small * factor,
            "t_small_s": t_small, "t_big_s": t_big, "ratio": ratio}


def run(fast: bool = True, smoke: bool = False):
    cfg, params = bench_model()
    rows, table = [], {}
    scaling = queue_scaling_gate()
    table["queue_scaling"] = scaling
    rows.append(row("serving/queue_scaling/ratio", 0.0,
                    round(scaling["ratio"], 2)))
    if smoke:
        trace_rep = serve_trace_point(cfg, params, n=6, tokens=6,
                                      verify=True)
        table["trace_multitenant"] = trace_rep
        save_artifact("serving_throughput.json", table)
        rows.append(row("serving/trace/tok_s", 0.0,
                        round(trace_rep["throughput_tok_s"], 2)))
        return rows
    n, tokens = (6, 8) if fast else (16, 24)
    for label, rate, policy, use_async in POINTS:
        rep, us = timed(serve_point, cfg, params, rate, policy, n,
                        tokens, use_async=use_async)
        table[label] = rep
        rows.append(row(f"serving/{label}/tok_s", us,
                        round(rep["throughput_tok_s"], 2)))
        rows.append(row(f"serving/{label}/ttft_ms", 0.0,
                        round(rep["ttft_mean_s"] * 1e3, 3)))
        rows.append(row(f"serving/{label}/tpot_ms", 0.0,
                        round(rep["tpot_mean_s"] * 1e3, 3)))
        rows.append(row(f"serving/{label}/req_per_load", 0.0,
                        round(rep["requests_per_load"], 2)))
    trace_rep, us = timed(serve_trace_point, cfg, params,
                          8 if fast else 24, tokens)
    table["trace_multitenant"] = trace_rep
    rows.append(row("serving/trace/tok_s", us,
                    round(trace_rep["throughput_tok_s"], 2)))
    for tname, tr in trace_rep["per_tenant"].items():
        rows.append(row(f"serving/trace/{tname}/ttft_p95_ms", 0.0,
                        round(tr["ttft_p95_s"] * 1e3, 3)))
    save_artifact("serving_throughput.json", table)
    sync_p, async_p = table["burst/overlap"], table["burst/overlap-async"]
    per = trace_rep["per_tenant"]
    record_bench("serving_throughput", {
        "profile": "fast" if fast else "full",
        "tok_s": sync_p["throughput_tok_s"],
        "async_tok_s": async_p["throughput_tok_s"],
        "tpot_ms": sync_p["tpot_mean_s"] * 1e3,
        "rehit_rate": async_p.get("rehit_rate", 0.0),
        "overlap_efficiency": async_p.get("overlap_efficiency", 0.0),
        "bytes_moved": sync_p["bytes_moved"],
        "async_bytes_moved": async_p["bytes_moved"],
        "requests_per_load": sync_p["requests_per_load"],
        "trace_tok_s": trace_rep["throughput_tok_s"],
        "trace_ttft_p95_ms_interactive":
            per["interactive"]["ttft_p95_s"] * 1e3,
        "trace_ttft_p95_ms_batch": per["batch"]["ttft_p95_s"] * 1e3,
        "trace_tpot_p95_ms_interactive":
            per["interactive"]["tpot_p95_s"] * 1e3,
        "trace_slo_ttft_interactive":
            per["interactive"]["ttft_slo_attainment"],
        "queue_scaling_ratio": scaling["ratio"],
    })
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: multi-tenant trace bit-exactness + "
                         "finite metrics + queue O(log n) scaling")
    args = ap.parse_args()
    for r in run(fast=not args.full, smoke=args.smoke):
        print(r)
