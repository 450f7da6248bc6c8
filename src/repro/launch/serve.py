"""Serving driver: OD-MoE cacheless engine on a (reduced) MoE model.

Single-stream mode (the paper's experiment driver):

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --tokens 32 --predictor sep --shadow int8

Continuous-batching mode (the ``repro.serve`` subsystem) — enabled by
``--requests``:

  PYTHONPATH=src python -m repro.launch.serve --requests 8 \
      --arrival-rate 2.0 --max-batch 4

Cluster mode (``repro.serve.cluster``) — N replica loops over ONE
shared worker fleet / expert store, with optional gate-stats expert
placement and compute-vs-ship wave scheduling:

  PYTHONPATH=src python -m repro.launch.serve --requests 16 \
      --replicas 2 --placement gate-stats --compute-vs-ship

Published widths (``--full``; the default ``--reduced`` is the small
same-family CPU model), in the config's own dtype, cut to ``--layers``:

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
      --full --layers 8 --tokens 16

Both run real prefill+decode through ``ODMoEEngine`` (prediction,
on-demand loading, alignment, eviction — all live) and verify outputs
match the dense reference bit-for-bit.  Serving mode drives Poisson
arrivals through ``ServingLoop`` — prefill-on-admission, SEP-overlap
batch composition — and reports per-request TTFT/TPOT plus aggregate
throughput from the timing model, alongside load-amortization stats
(how many requests each physical expert load served).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (AlignmentPolicy, ODMoEEngine, RTX3090_EDGE,
                        node_memory_report, simulate_cached, simulate_odmoe)
from repro.core.yardstick import check_decode, merge
from repro.fleet import (FleetSchedule, GateStatsRecorder,
                         expected_t_maxload, modulo_plan,
                         optimize_placement)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import greedy_generate, init_params
from repro.quant import (TieredPolicy, UniformPolicy, resolve_policy,
                         transport_params)
from repro.serve import (BatchComposer, KVPool, ServingLoop, WorkloadSpec,
                         dense_cache_footprint, make_cluster, make_trace,
                         make_traffic)
from repro.serve.cluster import ROUTING_POLICIES


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="small same-family fp32 model (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the config's published widths and dtype")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep the first N layers (0 = the "
                         "config's own depth)")
    ap.add_argument("--tokens", type=int, default=24,
                    help="decode length (serving: max new tokens/request)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--predictor", default="sep",
                    choices=["sep", "nextgate", "multigate", "freq",
                             "random", "none"])
    ap.add_argument("--shadow", default="int8",
                    choices=["fp16", "int8", "nf4"])
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--token-period", type=int, default=1)
    ap.add_argument("--kv-period", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--speculate", type=int, default=1,
                    help="draft-verify wave width k: the SEP shadow "
                         "drafts k tokens, one grouped wave verifies "
                         "them, the confirmed prefix commits — tokens "
                         "stay bit-identical to the reference, waves "
                         "get wider and fewer (k>1 requires "
                         "--predictor sep)")
    ap.add_argument("--transport-precision", default="fp32",
                    choices=["fp32", "fp16", "int8", "nf4", "tiered"],
                    help="on-demand expert wire precision (HOBBIT-style "
                         "mixed-precision transport); 'tiered' calibrates "
                         "a confidence-tiered fp16+int8 policy from a "
                         "short decode and verifies against the reference "
                         "under the same policy")
    ap.add_argument("--packed-slots", action="store_true",
                    help="packed-resident worker slots: keep the wire-"
                         "format codes+scales resident and dequantize "
                         "in-register inside the fused grouped kernel "
                         "(same tokens, ~4-8x smaller per-worker "
                         "footprint for int8/nf4 transport)")
    # ----------------------------------------------- serving mode flags
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N requests through continuous batching "
                         "(0 = single-stream mode)")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="Poisson arrival rate, requests/s of modeled "
                         "time (<=0: all arrive at t=0)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="composed decode batch cap")
    ap.add_argument("--compose", default="overlap",
                    choices=["overlap", "fifo", "fair"],
                    help="batch composition policy (fair: per-tenant "
                         "weighted deficit round-robin)")
    ap.add_argument("--workload", default="uniform",
                    choices=["uniform", "trace"],
                    help="'uniform' = the paper-style near-uniform mix "
                         "(make_traffic); 'trace' = trace-driven multi-"
                         "tenant traffic (repro.serve.workload): heavy-"
                         "tailed lengths, bursty/diurnal arrivals, "
                         "tenant classes with TTFT/TPOT SLOs")
    ap.add_argument("--arrival", default="bursty",
                    choices=["poisson", "bursty", "diurnal"],
                    help="arrival process for --workload trace")
    ap.add_argument("--preempt", default="youngest",
                    choices=["youngest", "slack"],
                    help="KV-page preemption victim policy: youngest "
                         "admission, or the request with the most TPOT-"
                         "deadline slack (best-effort traffic first)")
    ap.add_argument("--admit", default="fifo",
                    choices=["fifo", "priority"],
                    help="admission order: strict arrival FIFO, or "
                         "tenant-weight priority (interactive jumps "
                         "deferred batch traffic)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="serve decode KV out of a paged pool of this "
                         "many pages instead of dense per-request "
                         "buffers (0 = dense; budget-aware admission, "
                         "youngest-first preemption, page-exact resume)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="KV slots per page (with --kv-pages)")
    # ----------------------------------------------- cluster mode flags
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas over ONE shared worker fleet "
                         "/ expert store (>1 routes --requests traffic "
                         "through repro.serve.ClusterRouter)")
    ap.add_argument("--routing", default="least_loaded",
                    choices=list(ROUTING_POLICIES),
                    help="per-request replica routing policy "
                         "(with --replicas > 1)")
    ap.add_argument("--placement", default="modulo",
                    choices=["modulo", "gate-stats"],
                    help="expert placement: 'modulo' = the paper's "
                         "positional i mod G mapping; 'gate-stats' = "
                         "calibrate a GateStatsRecorder on a short "
                         "decode, then greedily place hot experts on "
                         "fast links to minimize expected per-wave "
                         "t_maxload (tokens stay bit-exact either way)")
    ap.add_argument("--compute-vs-ship", action="store_true",
                    help="price each cold expert's host-memory compute "
                         "against its worker link and keep the cheaper "
                         "side (MoNDE-style; scheduling only — same "
                         "round-tripped weights)")
    return ap


def build_transport(cfg, params, args):
    """Resolve --transport-precision into a PrecisionPolicy.  'tiered'
    runs a short full-precision calibration decode and tiers experts by
    mean gate weight (HOBBIT: low confidence -> cheap wire format)."""
    if args.transport_precision == "tiered":
        key = jax.random.PRNGKey(args.seed + 1)
        batch = {"tokens": jax.random.randint(key, (1, args.prompt_len), 0,
                                              cfg.vocab_size)}
        eng = ODMoEEngine(cfg, params, n_workers=args.workers,
                          predictor="none")
        _, trace = eng.generate(batch, max(8, args.tokens // 2))
        pol = TieredPolicy.from_trace(trace, low_fraction=0.5,
                                      num_experts=cfg.num_experts)
        print(f"  transport: calibrated {pol.describe()}")
        return pol
    return UniformPolicy(args.transport_precision)


def print_transport_stats(eng) -> None:
    """Codec accounting from the load-event log: what crossed the links
    vs the fp32 deployment payload for the same loads."""
    ev = eng.slots.events
    if not ev:
        return
    by_scheme = {}
    for e in ev:
        n, b = by_scheme.get(e.scheme, (0, 0))
        by_scheme[e.scheme] = (n + 1, b + e.bytes)
    fp32_equiv = len(ev) * eng.store.expert_bytes
    moved = eng.slots.bytes_moved
    print(f"  transport [{eng.transport.describe()}]: "
          f"{moved / 1e6:.2f} MB moved vs {fp32_equiv / 1e6:.2f} MB fp32 "
          f"({fp32_equiv / max(moved, 1):.2f}x reduction)")
    print("  loads by scheme: " + ", ".join(
        f"{s}={n} ({b / 1e6:.2f} MB)"
        for s, (n, b) in sorted(by_scheme.items())))


def build_placement(cfg, params, args):
    """--placement gate-stats: run a short calibration decode with a
    ``GateStatsRecorder``, optimize expert placement against the
    recorded routing distribution, and return a plan-carrying
    ``FleetSchedule`` (None for the default modulo mapping)."""
    if args.placement != "gate-stats":
        return None
    cal = GateStatsRecorder()
    eng = ODMoEEngine(cfg, params, n_workers=args.workers,
                      predictor="none", gate_stats=cal)
    key = jax.random.PRNGKey(args.seed + 2)
    batch = {"tokens": jax.random.randint(key, (1, args.prompt_len), 0,
                                          cfg.vocab_size)}
    eng.generate(batch, max(8, args.tokens // 2))
    g = max(cfg.top_k, 1)
    base = FleetSchedule(args.workers, g)
    kw = dict(num_experts=cfg.num_experts, n_moe=cal.n_layers)
    bkw = dict(kw, expert_bytes=eng.store.expert_bytes)
    plan = optimize_placement(cal, base, **bkw)
    e_opt = expected_t_maxload(plan, cal, base, **bkw)
    e_mod = expected_t_maxload(modulo_plan(base, **kw), cal, base, **bkw)
    print(f"  placement: gate-stats plan over {cal.n_layers} MoE layers"
          f" — expected t_maxload {e_opt * 1e3:.4f} ms"
          f" vs modulo {e_mod * 1e3:.4f} ms")
    return FleetSchedule(args.workers, g, plan=plan)


def engine_kwargs(cfg, params, args, transport) -> dict:
    """Engine construction kwargs shared by the single-loop, cluster
    and single-stream paths: predictor/transport plus the optional
    placement schedule and compute-vs-ship pricing."""
    kw = dict(predictor=args.predictor, shadow_scheme=args.shadow,
              transport=transport, speculate=args.speculate,
              packed_slots=args.packed_slots, keep_logits=True)
    sched = build_placement(cfg, params, args)
    if sched is not None:
        kw["sched"] = sched
    else:
        kw["n_workers"] = args.workers
    if args.compute_vs_ship:
        kw["compute_vs_ship"] = True
    return kw


def build_requests(cfg, args):
    if args.workload == "trace":
        spec = WorkloadSpec(n_requests=args.requests,
                            rate=args.arrival_rate, arrival=args.arrival,
                            prompt_median=args.prompt_len,
                            max_prompt=4 * args.prompt_len,
                            output_median=args.tokens,
                            max_output=2 * args.tokens)
        return make_trace(cfg, spec, seed=args.seed)
    return make_traffic(cfg, args.requests, args.arrival_rate,
                        prompt_len=args.prompt_len,
                        max_new=args.tokens, seed=args.seed)


def yardstick_ok(cfg, params, transport, runs) -> bool:
    """Token bit-identity with ``greedy_generate`` needs shared
    executables, which a TPU run does not have: judge the requests
    against the float32 reference instead (``repro.core.yardstick``).
    ``runs`` holds ``(prompt, tokens, records, logits)`` per request."""
    if not resolve_policy(transport).trivial:
        params = transport_params(cfg, params, transport)
    rep = merge([check_decode(cfg, params, *run) for run in runs])
    print(f"  vs float32 reference: {rep.describe()}")
    return rep.ok


def check_bit_exact(cfg, params, reqs, outputs, transport,
                    states=None) -> None:
    """Every served request must match its solo reference decode under
    the SAME transport policy — the cross-cutting correctness bar.  With
    the requests' ``states`` (their per-step traces), requests whose
    tokens differ may still pass the float32 yardstick."""
    differ = []
    for r in reqs:
        ref = np.asarray(greedy_generate(
            cfg, params, {"tokens": jnp.asarray(r.prompt)[None, :]},
            r.max_new_tokens, transport=transport))[0]
        if not np.array_equal(ref, outputs[r.rid]):
            differ.append(r)
    print(f"  per-request tokens == solo reference "
          f"(same transport policy): {not differ}")
    ok = not differ or (states is not None and yardstick_ok(
        cfg, params, transport,
        [(r.prompt, outputs[r.rid], states[r.rid].trace.records,
          states[r.rid].trace.logits) for r in differ]))
    assert ok, "serving output diverged from single-request reference"


def serve_cluster(cfg, params, args) -> None:
    transport = build_transport(cfg, params, args)
    gate_stats = GateStatsRecorder()
    engine_kw = dict(engine_kwargs(cfg, params, args, transport),
                     gate_stats=gate_stats)
    reqs = build_requests(cfg, args)
    router = make_cluster(cfg, params, replicas=args.replicas,
                          policy=args.routing, engine_kw=engine_kw,
                          loop_kw=dict(max_batch=args.max_batch))
    res = router.run(reqs)
    check_bit_exact(cfg, params, reqs, res.outputs, transport)
    rep = res.report()
    print(f"  cluster: {rep['replicas']} replicas, routing="
          f"{res.policy}, requests: {rep['n_requests']}, "
          f"tokens: {rep['total_tokens']}")
    for m in ("ttft", "tpot"):
        print(f"  {m.upper()}  mean {rep[f'{m}_mean_s'] * 1e3:.2f} ms   "
              f"p50 {rep[f'{m}_p50_s'] * 1e3:.2f}   "
              f"p95 {rep[f'{m}_p95_s'] * 1e3:.2f}   "
              f"p99 {rep[f'{m}_p99_s'] * 1e3:.2f}")
    print(f"  throughput: {rep['throughput_tok_s']:.2f} tok/s over "
          f"{rep['makespan_s']:.3f} s makespan")
    for i, rr in enumerate(rep["per_replica"]):
        print(f"  [replica {i}] n={rr['requests']}  "
              f"mean batch {rr['mean_batch']:.2f}  "
              f"TTFT p95 {rr['ttft_p95_s'] * 1e3:.2f} ms")
    if res.autoscale_events:
        print(f"  autoscale events: {res.autoscale_events}")
    print(f"  pooled gate stats: {gate_stats.n_layers} MoE layers, "
          f"{sum(gate_stats.rows.values())} routed rows")


def run_traffic(cfg, params, args):
    """Serve ``build_requests`` traffic through one ``ServingLoop``:
    returns ``(requests, result, engine, transport, kv_pool)``."""
    transport = build_transport(cfg, params, args)
    eng = ODMoEEngine(cfg, params,
                      **engine_kwargs(cfg, params, args, transport))
    policy = AlignmentPolicy(args.token_period, args.kv_period)
    reqs = build_requests(cfg, args)
    kv_pool = (KVPool(cfg, num_pages=args.kv_pages,
                      page_tokens=args.page_tokens)
               if args.kv_pages else None)
    loop = ServingLoop(eng, max_batch=args.max_batch,
                       composer=BatchComposer(args.max_batch, args.compose,
                                              kv_pool=kv_pool),
                       policy=policy, kv_pool=kv_pool,
                       preempt=args.preempt, admit=args.admit)
    return reqs, loop.run(reqs), eng, transport, kv_pool


def serve_traffic(cfg, params, args) -> None:
    reqs, res, eng, transport, kv_pool = run_traffic(cfg, params, args)
    check_bit_exact(cfg, params, reqs, res.outputs, transport, res.states)
    # ---- latency / throughput report (modeled edge profile)
    rep = res.timings.report()
    print(f"  requests: {rep['n_requests']}  tokens: {rep['total_tokens']}"
          f"  mean batch: {res.mean_batch:.2f}")
    for m in ("ttft", "tpot"):
        print(f"  {m.upper()}  mean {rep[f'{m}_mean_s'] * 1e3:.2f} ms   "
              f"p50 {rep[f'{m}_p50_s'] * 1e3:.2f}   "
              f"p95 {rep[f'{m}_p95_s'] * 1e3:.2f}   "
              f"p99 {rep[f'{m}_p99_s'] * 1e3:.2f}")
    print(f"  throughput: {rep['throughput_tok_s']:.2f} tok/s over "
          f"{rep['makespan_s']:.3f} s makespan")
    if args.workload == "trace":
        print(f"  trace: {args.arrival} arrivals, preempt={args.preempt},"
              f" admit={args.admit}, compose={args.compose}")
        for name, tr in res.tenant_report().items():
            print(f"  [{name}] n={tr['n_requests']}  "
                  f"TTFT p50/p95/p99 {tr['ttft_p50_s'] * 1e3:.2f}/"
                  f"{tr['ttft_p95_s'] * 1e3:.2f}/"
                  f"{tr['ttft_p99_s'] * 1e3:.2f} ms  "
                  f"TPOT p95 {tr['tpot_p95_s'] * 1e3:.2f} ms  "
                  f"SLO ttft {tr['ttft_slo_attainment']:.2f} "
                  f"tpot {tr['tpot_slo_attainment']:.2f}")
    if res.spec_stats is not None:
        ss = res.spec_stats
        print(f"  speculation k={ss['speculate']}: acceptance "
              f"{ss['acceptance']:.3f} over {len(ss['per_request'])} "
              f"requests")
    # ---- amortization: requests served per physical load
    ev = eng.slots.events
    served = [len(e.requests) for e in ev if e.requests]
    if served:
        print(f"  loads: {len(ev)}  mean requests/load: "
              f"{np.mean(served):.2f}  multi-request loads: "
              f"{sum(1 for s in served if s > 1)}/{len(served)}")
    print(f"  load stats: {eng.slots.stats}")
    print_transport_stats(eng)
    # ---- KV pool occupancy + per-node memory (paged serving)
    if kv_pool is not None:
        st = res.kv_stats
        occ = [s.kv_pages_used for s in res.steps if s.kv_pages_used >= 0]
        dense = dense_cache_footprint(
            cfg, kv_pool.window_pages * kv_pool.page_tokens, len(reqs))
        print(f"  kv pool: {st['num_pages']} pages x "
              f"{st['page_tokens']} tokens = {st['pool_bytes'] / 1e6:.2f} MB"
              f" (dense footprint for {len(reqs)} requests: "
              f"{dense / 1e6:.2f} MB)")
        print(f"  occupancy: peak {st['peak_pages_used']}"
              f"/{st['num_pages']} pages"
              + (f", mean {np.mean(occ):.1f}" if occ else "")
              + f"  deferred admissions: {st['deferred_admissions']}")
        print(f"  preemptions: {st['preemptions']}  resumes: "
              f"{st['resumes']}  swapped: "
              f"{(st['swap_out_bytes'] + st['swap_in_bytes']) / 1e6:.2f} MB"
              f" ({st['swap_s'] * 1e3:.3f} ms modeled)")
    mem = node_memory_report(eng, kv_pool)
    print("  per-node memory: " + ", ".join(
        f"{k}={v / 1e6:.2f}MB" for k, v in mem.items()
        if k.endswith("bytes")))
    # per-request wire bytes: each load's packed payload credited to
    # every request riding it (amortized codec accounting)
    per_req = {r.rid: 0 for r in reqs}
    for e in ev:
        for rid in e.requests:
            if rid in per_req:
                per_req[rid] += e.bytes
    if any(per_req.values()):
        vals = list(per_req.values())
        print(f"  wire bytes/request: mean {np.mean(vals) / 1e6:.2f} MB  "
              f"max {max(vals) / 1e6:.2f} MB")


def run_single(cfg, params, args):
    """Single-stream decode of one seeded prompt: returns
    ``(batch, tokens, trace, engine, transport)``."""
    key = jax.random.PRNGKey(args.seed)
    batch = {"tokens": jax.random.randint(key, (1, args.prompt_len), 0,
                                          cfg.vocab_size)}
    transport = build_transport(cfg, params, args)
    eng = ODMoEEngine(cfg, params,
                      **engine_kwargs(cfg, params, args, transport))
    policy = AlignmentPolicy(args.token_period, args.kv_period)
    toks, trace = eng.generate(batch, args.tokens, policy)
    return batch, toks, trace, eng, transport


def serve_single(cfg, params, args) -> None:
    batch, toks, trace, eng, transport = run_single(cfg, params, args)
    ref = greedy_generate(cfg, params, batch, args.tokens,
                          transport=transport)
    exact = bool(np.array_equal(np.asarray(toks), np.asarray(ref)))
    print(f"  tokens == dense reference (same transport policy): {exact}")
    assert exact or yardstick_ok(
        cfg, params, transport, [(np.asarray(batch["tokens"])[0],
                                  np.asarray(toks)[0], trace.records,
                                  trace.logits)]), \
        "engine output diverged from reference"
    rec = trace.recall()      # None when nothing was predicted
    print(f"  recall (Eq.3): "
          f"{'n/a (no predictions)' if rec is None else f'{rec:.4f}'}   "
          f"reload fraction: {trace.reload_fraction():.4f}")
    if args.speculate > 1:
        drafted = sum(r.spec_len for r in trace.records)
        committed = sum(r.committed for r in trace.records)
        print(f"  speculation k={args.speculate}: acceptance "
              f"{committed / max(drafted, 1):.3f} over "
              f"{len(trace.records)} waves")
    print(f"  loads: {eng.slots.stats}")
    print_transport_stats(eng)
    mem = eng.memory_report()
    print("  memory: " + ", ".join(
        f"{k}={v/1e6:.2f}MB" for k, v in mem.items() if k.endswith("bytes")))
    t = simulate_odmoe(cfg, trace, eng.sched, RTX3090_EDGE,
                       shadow_scheme=args.shadow,
                       predictor=args.predictor, transport=transport)
    print(f"  modeled decode speed ({RTX3090_EDGE.name}): "
          f"{t.tokens_per_s:.2f} tok/s "
          f"(fully-cached reference {simulate_cached(cfg, RTX3090_EDGE):.2f})")


def build_config(args):
    """``--arch`` at reduced or published widths, cut to ``--layers``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg = build_config(args)
    if not cfg.num_experts:
        raise SystemExit(f"{args.arch} has no experts — OD-MoE loading is "
                         "inapplicable (see DESIGN.md §4); serve it with "
                         "examples/quickstart.py instead.")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.replicas > 1 and not args.requests:
        raise SystemExit("--replicas > 1 needs --requests traffic")
    mode = (f"continuous batching: {args.requests} {args.workload} "
            f"requests @ {args.arrival_rate}/s, max-batch "
            f"{args.max_batch} ({args.compose})"
            + (f", {args.replicas} replicas ({args.routing})"
               if args.replicas > 1 else "")
            if args.requests else "single stream")
    print(f"[serve] {cfg.name}: d={cfg.d_model} L={cfg.num_layers} "
          f"{cfg.dtype}, E={cfg.num_experts} top{cfg.top_k}, "
          f"{args.workers} workers, predictor={args.predictor}"
          + (f"/{args.shadow}" if args.predictor == "sep" else "")
          + f", transport={args.transport_precision}"
          + f", placement={args.placement}"
          + (", compute-vs-ship" if args.compute_vs_ship else "")
          + f" — {mode}")
    if args.requests and args.replicas > 1:
        serve_cluster(cfg, params, args)
    elif args.requests:
        serve_traffic(cfg, params, args)
    else:
        serve_single(cfg, params, args)


if __name__ == "__main__":
    main()
