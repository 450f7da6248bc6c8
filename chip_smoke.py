"""One-chip smoke run of the OD-MoE serving path at published widths.

Builds Granite-MoE-3B-A800M at its published widths in bfloat16 (d=1536,
24 heads / 8 KV heads, 40 experts top-8, d_expert=512, vocab 49155), cut
to its first 8 of 32 layers, with random weights from ``--seed``.  It then
drives the model through the code ``python -m repro.launch.serve --full``
runs:

  (a) one 16-token prompt decoded for 16 tokens by ``ODMoEEngine.generate``
      with the SEP shadow predicting the expert loads;
  (b) four requests arriving at once, served by a ``ServingLoop`` with a
      composed batch of up to 4 (16-token prompts, 16 new tokens).

Each phase is checked twice: token for token against ``greedy_generate``
(the serving CLI's own gate), and by ``repro.core.yardstick`` against a
float32 ``precision="highest"`` forward of the same weights, step by step
on routing and logits.  The second check decides the result, because
bit-identity only holds where engine and reference run the same
executables; where the tokens differ, the first layer whose hidden state
differs is printed.

The run needs a TPU: on any other platform it exits 1 and prints no
result.  Its last stdout line is one JSON object:

    python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
# 8 of 32 layers: the engine keeps three copies of the weights on the
# device (params, per-layer slices, SEP shadow), ~2.1 GB each at this depth
LAYERS = 8
TOKENS = 16
PROMPT_LEN = 16
REQUESTS = 4


def serve_args(seed: int, *, requests: int = 0):
    """The ``repro.launch.serve`` arguments of each phase."""
    from repro.launch.serve import build_parser
    argv = ["--arch", ARCH, "--full", "--layers", str(LAYERS),
            "--tokens", str(TOKENS), "--prompt-len", str(PROMPT_LEN),
            "--predictor", "sep", "--shadow", "int8", "--seed", str(seed)]
    if requests:
        argv += ["--requests", str(requests), "--arrival-rate", "0",
                 "--max-batch", "4"]
    return build_parser().parse_args(argv)


# ------------------------------------------------------------- checks
def first_divergent_layer(eng, cfg, params, batch):
    """One decode step from the shared prefill, by the engine and by
    ``greedy_generate``'s compiled step: the first layer whose new K/V
    entry differs is the first layer whose input hidden state differs.
    Returns a one-line description."""
    import jax
    import numpy as np
    from repro.core.engine import TokenRecord
    from repro.models import prefill
    from repro.models.api import _jit_decode_step
    max_len = batch["tokens"].shape[1] + 2
    token, cache_list, pos = eng.prefill_request(batch, max_len)
    eng.decode_batch(token, cache_list, pos, {}, 0,
                     TokenRecord(index=0, aligned_token=False,
                                 aligned_kv=False))
    logits, state = prefill(cfg, params, batch, max_len,
                            moe_method="grouped")
    ref_logits, ref_state = _jit_decode_step(cfg, "grouped")(
        params, token, state)
    period = len(cfg.pattern()[0])
    for li in range(cfg.num_layers):
        ref_c = jax.tree.map(lambda a: a[li // period],
                             ref_state["caches"][li % period])
        ke = np.asarray(cache_list[li]["k"], np.float32)
        kr = np.asarray(ref_c["k"], np.float32)
        vs = np.array_equal(np.asarray(cache_list[li]["v"]),
                            np.asarray(ref_c["v"]))
        if not (np.array_equal(ke, kr) and vs):
            d = np.abs(ke - kr)
            return (f"first layer whose K/V differ = {li} ({int(np.sum(d > 0))}"
                    f" of {d.size} K entries, max |dK| {float(d.max())} at "
                    f"max |K| {float(np.abs(kr).max())})")
    same = np.array_equal(np.asarray(eng.last_logits),
                          np.asarray(ref_logits))
    return f"all K/V equal, logits bit-equal: {same}"


def grouped_gemm_is_kernel(cfg) -> bool:
    """Whether the compiled decode-wave step (``_grouped_contrib`` on a
    wave of ``top_k`` bf16 slot experts) holds the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.moe_gemm import ops
    d, f, k = cfg.d_model, cfg.d_expert_resolved, cfg.top_k
    wdt = jnp.dtype(cfg.dtype)
    s = jax.ShapeDtypeStruct
    compiled = ops._grouped_contrib.lower(
        s((1, d), wdt), s((k, d, f), wdt), s((k, d, f), wdt),
        s((k, f, d), wdt), s((1, k), jnp.int32),
        s((1, k), jnp.float32)).compile()
    return "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------- phases
def phase_single(cfg, params, args, log) -> bool:
    import jax
    import numpy as np
    from repro.core import AlignmentPolicy
    from repro.core.yardstick import check_decode
    from repro.launch.serve import run_single
    from repro.models import greedy_generate
    t = time.perf_counter()
    batch, toks, trace, eng, transport = run_single(cfg, params, args)
    jax.block_until_ready(toks)
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    toks2, _ = eng.generate(batch, args.tokens,
                            AlignmentPolicy(args.token_period,
                                            args.kv_period))
    jax.block_until_ready(toks2)
    warm_s = time.perf_counter() - t
    log(f"[a] single stream: {args.tokens} tokens, predictor=sep/int8; "
        f"first generate {first_s:.3f} s, warm generate {warm_s:.3f} s "
        f"(host clock, after block_until_ready; prefill + "
        f"{args.tokens - 1} decode steps)")
    ref = greedy_generate(cfg, params, batch, args.tokens,
                          transport=transport)
    exact = bool(np.array_equal(np.asarray(toks), np.asarray(ref)))
    repeat = bool(np.array_equal(np.asarray(toks), np.asarray(toks2)))
    n_same = int(np.sum(np.asarray(toks) == np.asarray(ref)))
    log(f"[a] tokens == greedy_generate: {exact} ({n_same}/{args.tokens} "
        f"equal); second generate repeats the first: {repeat}")
    rec = trace.recall()
    log(f"[a] SEP recall {rec if rec is None else round(rec, 4)}, "
        f"loads {eng.slots.stats['loads']}, "
        f"reloads {eng.slots.stats['reloads']}")
    if not exact:
        log(f"[a] engine vs greedy_generate after one decode step: "
            f"{first_divergent_layer(eng, cfg, params, batch)}")
    report = check_decode(cfg, params, np.asarray(batch["tokens"])[0],
                          np.asarray(toks)[0], trace.records, trace.logits)
    log(f"[a] vs float32 reference: {report.describe()}")
    eng.close()
    return report.ok and repeat


def phase_serving(cfg, params, args, log) -> bool:
    import jax
    import numpy as np
    from repro.core.yardstick import check_decode, merge
    from repro.launch.serve import run_traffic
    from repro.models import greedy_generate
    t = time.perf_counter()
    reqs, res, eng, transport, _ = run_traffic(cfg, params, args)
    serve_s = time.perf_counter() - t
    n_tok = sum(len(v) for v in res.outputs.values())
    log(f"[b] serving: {len(reqs)} requests at once, max batch "
        f"{args.max_batch}, mean batch {res.mean_batch:.2f}, {n_tok} "
        f"tokens in {serve_s:.3f} s (host clock, compile included)")
    ok = len(res.outputs) == len(reqs)
    exact_all = True
    reports = []
    for r in reqs:
        out = res.outputs[r.rid]
        ref = np.asarray(greedy_generate(
            cfg, params, {"tokens": jax.numpy.asarray(r.prompt)[None, :]},
            r.max_new_tokens, transport=transport))[0]
        exact_all &= bool(np.array_equal(ref, out))
        ok &= len(out) == r.max_new_tokens
        trace = res.states[r.rid].trace
        reports.append(check_decode(cfg, params, r.prompt, out,
                                    trace.records, trace.logits))
        log(f"[b] request {r.rid} vs float32 reference: "
            f"{reports[-1].describe()}")
    log(f"[b] per-request tokens == solo greedy_generate: {exact_all}")
    report = merge(reports)
    log(f"[b] all requests vs float32 reference: {report.describe()}")
    eng.close()
    return ok and report.ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.kernels.moe_gemm import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.launch.serve import build_config
    from repro.models import init_params

    def log(msg):
        print(msg, flush=True)

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({warm} entries at start)")
    log(f"device: {dev.device_kind} x{len(jax.devices())} "
        f"(platform {dev.platform})")
    args = serve_args(opts.seed)
    cfg = build_config(args)
    log(f"config: {cfg.name} [{cfg.source}] d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"experts={cfg.num_experts} top_k={cfg.top_k} "
        f"d_expert={cfg.d_expert_resolved} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype}; depth cut to {cfg.num_layers} of "
        f"{get_config(ARCH).num_layers} layers")
    params = init_params(cfg, jax.random.PRNGKey(opts.seed))
    jax.block_until_ready(params)
    ok_kernel = ops._on_tpu() and grouped_gemm_is_kernel(cfg)
    log(f"grouped GEMM executable contains tpu_custom_call: {ok_kernel}")
    ok_a = phase_single(cfg, params, args, log)
    gc.collect()
    ok_b = phase_serving(cfg, params,
                         serve_args(opts.seed, requests=REQUESTS), log)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log(f"backend compile seconds: {sum(compile_s):.3f} over "
        f"{len(compile_s)} compiles")
    failed = [name for name, good in (("kernel", ok_kernel),
                                      ("single", ok_a), ("serving", ok_b))
              if not good]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
