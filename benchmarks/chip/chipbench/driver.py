"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives the program's serving entry: ``ServingLoop``
(``start`` / ``add_request`` / ``tick``) over an ``ODMoEEngine`` with
the SEP int8 shadow, on-demand expert loads and the grouped expert GEMM.
The harness is the client side: it makes the requests from the seed,
adds each one when it is due (``arrival_s=0``, so the loop's modelled
clock never holds it back), ticks the loop while there is work, and
stamps every output token on the host's clock.  A request is timed from
when it was due.  Nothing here reads the loop's modelled clock.

Reads through the loop's private attributes (listed in PERF.md for the
program to expose): ``ServingLoop._queue`` (``_active``, ``finished``),
``ServingLoop._trace`` and ``._steps`` (the composed-step records) and
``ODMoEEngine._compute_wave`` (warm-up of every wave size).
"""
from __future__ import annotations

import gc
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import config as config_mod
from . import spec, stats, traffic, yardstick

WARMUP_RID = 1 << 30
POST_WINDOW_S = 60.0


class NoChip(RuntimeError):
    pass


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_compile_cache(cache_dir: str) -> str:
    """JAX's persistent compilation cache at one fixed directory, every
    executable cached (no minimum compile time or size)."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCounter:
    """Counts executables built or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0
        self.names: List[str] = []

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs
                self.names.append(str(kw.get("fun_name", "?")))
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


@dataclass
class Client:
    """One request in flight, from the client's side."""
    req: traffic.GenRequest
    due: float
    stamps: List[float] = field(default_factory=list)
    sent: bool = False
    client: int = -1            # closed loop: which caller sent it
    done: bool = False


@dataclass
class RunRecord:
    """What a run hands the metric readers."""
    cell: object
    cfg: object                 # ModelConfig
    plugin: object              # the architecture plug-in (its FLOP count)
    mix: traffic.Mix
    window: tuple               # host seconds (t0, t_end)
    clients: List[Client]
    steps: list                 # composed-step records in the window
    records: list               # TokenRecords of the window's decode steps
    prefills: List[int]         # prompt lengths prefilled in the window
    counters: Dict[str, float]
    peaks: object = None
    trace: object = None        # chipbench.trace.Trace (traced runs)


def release(state, logits: bool = False) -> None:
    """Drop a finished request's device state (its KV caches, shadow
    state and peek; with ``logits`` also the logits kept for the
    check)."""
    state.cache_list, state.shadow_state, state.pending = [], None, None
    if logits:
        state.trace.logits.clear()


def _served_state(loop, rid):
    q = loop._queue
    return q._active.get(rid) or q.finished.get(rid)


class Harness:
    def __init__(self, bench_cfg, mix: traffic.Mix, params):
        from repro.core import ODMoEEngine
        from repro.serve import ServingLoop
        self.cfg = bench_cfg.model
        self.mix = mix
        self.engine = ODMoEEngine(
            self.cfg, params, n_workers=bench_cfg.n_workers,
            predictor=bench_cfg.predictor,
            shadow_scheme=bench_cfg.shadow_scheme, keep_logits=True)
        self.loop = ServingLoop(self.engine, max_batch=mix.max_batch)
        self.loop.start([], cache_len=mix.cache_window)
        self.first_stamps: List[float] = []
        self.prefills: List[tuple] = []     # (stamp, prompt length)
        self._wrap_prefill()

    # ------------------------------------------------------ instruments
    def _wrap_prefill(self):
        eng, orig = self.engine, self.engine.prefill_request

        def prefill_request(batch, *a, **kw):
            out = orig(batch, *a, **kw)
            out[0].block_until_ready()        # the loop reads it next
            self.first_stamps.append(time.perf_counter())
            self.prefills.append((self.first_stamps[-1],
                                  int(batch["tokens"].shape[1])))
            return out
        eng.prefill_request = prefill_request

    def add_spans(self):
        """Host spans for the traced run: a wrapper whose target has
        gone is left out."""
        import jax
        targets = [(self.engine, "prefill_request", "bench.prefill"),
                   (self.engine, "decode_batch", "bench.decode_batch"),
                   (self.engine.slots, "load", "bench.expert_load"),
                   (self.engine.shadow, "step_state", "bench.shadow_step")]
        for obj, attr, label in targets:
            fn = getattr(obj, attr, None)
            if fn is None:
                continue

            def wrapped(*a, _fn=fn, _label=label, **kw):
                with jax.profiler.TraceAnnotation(_label):
                    return _fn(*a, **kw)
            setattr(obj, attr, wrapped)

    # ----------------------------------------------------------- serving
    def _request(self, rid: int, prompt, max_new: int):
        from repro.serve import Request
        return Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                       arrival_s=0.0)

    def tick(self, label: Optional[str] = None) -> bool:
        if label is None:
            return self.loop.tick()
        import jax
        with jax.profiler.TraceAnnotation(label):
            return self.loop.tick()

    def serve_all(self, reqs) -> None:
        """Serve warm-up requests to the end, then drop their device
        state, so the next one starts from the same memory."""
        for r in reqs:
            self.loop.add_request(r)
        while self.loop.has_work():
            self.loop.tick()
        for r in reqs:
            release(_served_state(self.loop, r.rid), logits=True)
        gc.collect()

    def warm_up(self, rng: np.random.Generator) -> None:
        """Every shape the cell's traffic uses, and no other: one prompt
        of each length the mix sends (the prefill pads each length to
        its bucket with a program of its own), composed decode steps of
        1..max_batch rows, and the grouped GEMM at every wave size for
        those row counts, all in the one cache window."""
        vocab = self.cfg.vocab_size
        rid = WARMUP_RID
        for n in traffic.prompt_lengths(self.mix):
            self.serve_all([self._request(
                rid, rng.integers(0, vocab, n).astype(np.int32), 1)])
            rid += 1
        lo = int(self.mix.prompt["lo"])
        for b in range(1, self.mix.max_batch + 1):
            self.serve_all([self._request(
                rid + i, rng.integers(0, vocab, lo).astype(np.int32), 3)
                for i in range(b)])
            rid += b
        self._warm_waves()
        self.first_stamps.clear()
        self.prefills.clear()

    def _warm_waves(self) -> None:
        """The grouped GEMM at every (rows, wave size) the traffic can
        make: ``b`` rows route to ``k..b*k`` distinct experts, served in
        waves of at most one expert per worker."""
        import jax
        import jax.numpy as jnp
        eng, cfg = self.engine, self.cfg
        layer, k = eng.moe_layers[0], cfg.top_k
        n_w = eng.sched.n_workers
        for b in range(1, self.mix.max_batch + 1):
            h = jnp.zeros((b, cfg.d_model), jnp.dtype(cfg.dtype))
            gates = np.full((b, k), 1.0 / k, np.float32)
            true = np.arange(b * k).reshape(b, k) % cfg.num_experts
            for n in wave_sizes(b, k, n_w, cfg.num_experts):
                wave = {e: e for e in range(n)}
                for e, w in wave.items():
                    eng.slots.load(-1, layer, e, w, predicted=True)
                out = eng._compute_wave(layer, h, true, gates, wave, None)
                jax.block_until_ready(eng._compute_wave(
                    layer, h, true, gates, wave, out))
                for w in wave.values():
                    eng.slots.evict(w)

    # ------------------------------------------------------------ window
    def run_window(self, reqs: List[traffic.GenRequest], seconds: float,
                   label: Optional[str]) -> tuple:
        """Drive the loop for ``seconds``; returns ``(t0, t_end, clients,
        first index of the window's records and steps)``."""
        loop, mix = self.loop, self.mix
        queue = list(reqs)
        clients: Dict[int, Client] = {}
        pending_first: List[int] = []
        rec0, step0 = len(loop._trace.records), len(loop._steps)
        t0 = time.perf_counter()
        deadline = t0 + seconds
        if mix.loop == "closed":
            free_at = [t0] * mix.clients
        t_end = None

        def send_due(now: float, closing: bool):
            if mix.loop == "closed":
                for c in range(len(free_at)):
                    if free_at[c] is not None and free_at[c] <= now \
                            and queue and not (closing and free_at[c] > t_end):
                        r = queue.pop(0)
                        clients[r.index] = Client(r, due=free_at[c],
                                                  client=c)
                        free_at[c] = None
            else:
                while queue and t0 + queue[0].due_s <= now \
                        and not (closing and t0 + queue[0].due_s > t_end):
                    r = queue.pop(0)
                    clients[r.index] = Client(r, due=t0 + r.due_s)
            for cl in clients.values():
                if not cl.sent:
                    loop.add_request(self._request(
                        cl.req.index, cl.req.prompt, cl.req.max_new_tokens))
                    cl.sent = True
                    pending_first.append(cl.req.index)

        def after_tick(now: float):
            # first tokens stamped when prefill returned, in admission order
            admitted = [rid for rid in pending_first
                        if _served_state(loop, rid) is not None]
            admitted.sort(key=lambda rid: _served_state(loop, rid).admit_seq)
            for rid in admitted:
                clients[rid].stamps.append(self.first_stamps.pop(0))
                pending_first.remove(rid)
            for rid, cl in clients.items():
                st = _served_state(loop, rid)
                if st is None:
                    continue
                while len(cl.stamps) < len(st.generated):
                    cl.stamps.append(now)
                if not cl.done and rid in loop._queue.finished:
                    cl.done = True
                    if mix.loop == "closed":
                        free_at[cl.client] = cl.stamps[-1]
                    release(st)

        import contextlib
        import jax
        ctx = (jax.profiler.TraceAnnotation(label) if label
               else contextlib.nullcontext())
        with ctx:
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    t_end = now
                    break
                send_due(now, closing=False)
                if loop.has_work():
                    self.tick("bench.tick" if label else None)
                    after_tick(time.perf_counter())
                elif mix.loop == "open" and queue:
                    time.sleep(max(0.0, min(deadline, t0 + queue[0].due_s)
                                   - time.perf_counter()))
                else:
                    time.sleep(0.001)
        # requests due in the window still get their first token
        send_due(t_end, closing=True)
        limit = time.perf_counter() + POST_WINDOW_S
        while pending_first and loop.has_work() \
                and time.perf_counter() < limit:
            self.tick()
            after_tick(time.perf_counter())
        return t0, t_end, list(clients.values()), rec0, step0

    def served(self, rid: int) -> yardstick.Served:
        st = _served_state(self.loop, rid)
        routing = [{lr.layer: np.asarray(lr.true[0]) for lr in rec.layers}
                   for rec in st.trace.records]
        logits = [np.asarray(l, np.float32)[0] for l in st.trace.logits]
        n = 1 + len(routing)
        return yardstick.Served(
            prompt=np.asarray(st.request.prompt, np.int32),
            tokens=np.asarray(st.generated[:n], np.int32),
            routing=routing, logits=logits)


def wave_sizes(rows: int, k: int, workers: int, experts: int):
    """Sizes of the expert waves a decode step of ``rows`` rows can run:
    its ``k..rows*k`` distinct experts go out in full waves of one
    expert per worker and a last partial one."""
    sizes = set()
    for distinct in range(k, min(rows * k, experts) + 1):
        if distinct >= workers:
            sizes.add(workers)
        if distinct % workers:
            sizes.add(distinct % workers)
    return sorted(sizes)


def sample_for_check(clients: List[Client], check: dict, seed: int
                     ) -> List[int]:
    """Requests to judge, drawn from the seed: the one with the most
    tokens first, then others in a seeded order, until ``max_requests``
    or ``max_tokens`` is reached."""
    have = [c for c in clients if len(c.stamps) > 1]
    if not have:
        return []
    longest = max(have, key=lambda c: (len(c.stamps), -c.req.index))
    rest = [c for c in have if c is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, tokens = [longest], len(longest.stamps)
    for i in order:
        if len(picked) >= check["max_requests"] \
                or tokens >= check["max_tokens"]:
            break
        picked.append(rest[i])
        tokens += len(rest[i].stamps)
    return [c.req.index for c in picked]


def end_to_end(clients: List[Client], t0: float, t_end: float
               ) -> Dict[str, Optional[float]]:
    window = t_end - t0
    tokens = sum(sum(t0 <= s <= t_end for s in c.stamps) for c in clients)
    gaps = [g for c in clients for g in stats.window_gaps(c.stamps, t0,
                                                          t_end)]
    ttft = [c.stamps[0] - c.due for c in clients
            if c.due <= t_end and c.stamps]
    return {"decode_tok_s": stats.rate(tokens, window),
            "tpot_p90_ms": (None if not gaps
                            else stats.percentile(gaps, 90) * 1e3),
            "ttft_p50_ms": (None if not ttft
                            else stats.percentile(ttft, 50) * 1e3),
            "tokens": tokens, "gaps": len(gaps), "requests": len(ttft)}


def load_limits(cell_name: str, base=spec.BENCH_DIR) -> Dict[str, float]:
    return spec.load_json("limits", cell_name, base)["limits"]


def run(cell_name: str, seed: int, seconds: float, traced: bool, log,
        *, t_start: float, require_chip: bool = True, base=spec.BENCH_DIR,
        spec_file=None, fault: Optional[Callable] = None,
        compile_cache: bool = True, keep_served: bool = False) -> dict:
    """One run.  Returns the result object (the harness prints it);
    with ``keep_served`` also the judged requests, the weights, the
    architecture plug-in and its ``Arch``, for the control."""
    cell = spec.load_cell(cell_name, spec_file)
    if require_chip:
        devs = check_devices(cell.chips)
    else:
        import jax
        devs = jax.devices()
    import jax
    from . import peaks as peaks_mod
    from . import trace as trace_mod
    cache = (enable_compile_cache(str(base / ".cache" / "jax"))
             if compile_cache else "off")
    counter = CompileCounter()
    bench_cfg = config_mod.load(cell.config, base)
    mix = traffic.Mix.from_dict(spec.load_json("traffic", cell.traffic,
                                               base))
    limits = load_limits(cell_name, base)
    dev = devs[0]
    peak_table = peaks_mod.for_kind(dev.device_kind) if require_chip \
        else None
    log(f"cell {cell_name}: config {bench_cfg.name}, traffic {mix.name}, "
        f"seed {seed}, {seconds} s, trace {int(traced)}; device "
        f"{dev.device_kind} x{len(devs)}; compile cache {cache}")
    t = time.perf_counter()
    plugin = bench_cfg.plugin
    params = plugin.make_params(bench_cfg.model, seed)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    h = Harness(bench_cfg, mix, params)
    t_engine = time.perf_counter() - t
    if fault is not None:
        fault(h)
    reqs = traffic.make_requests(mix, bench_cfg.model.vocab_size, seed)
    t = time.perf_counter()
    h.warm_up(np.random.default_rng([seed, 1]))
    gc.collect()
    setup_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"set-up: weights {t_weights:.3f} s, engine {t_engine:.3f} s, "
        f"warm-up {time.perf_counter() - t:.3f} s; {counter.compiles} "
        f"compiles ({counter.compile_s:.3f} s), {counter.cache_loads} "
        f"cache loads; peak {setup_peak} bytes")
    trace_dir = str(base / ".cache" / "trace" / cell_name)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        h.add_spans()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # our spans only, not every call
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = dict(counter.__dict__, names=list(counter.names))
    loads0 = h.engine.slots.stats["loads"]
    setup_s = time.perf_counter() - t_start
    t0, t_end, clients, rec0, step0 = h.run_window(
        reqs, seconds, trace_mod.WINDOW_SPAN if traced else None)
    window_compiles = (counter.compiles - before["compiles"]
                       + counter.cache_loads - before["cache_loads"])
    if traced:
        jax.effects_barrier()
        jax.profiler.stop_trace()
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    e2e = end_to_end(clients, t0, t_end)
    steps = h.loop._steps[step0:]
    records = h.loop._trace.records[rec0:]
    counters = {"loads": h.engine.slots.stats["loads"] - loads0,
                "decoded_tokens": sum(len(s.request_ids) for s in steps)}
    if window_compiles:
        log("compiled in the window: "
            + ", ".join(counter.names[len(before["names"]):]))
    log(f"window: {t_end - t0:.3f} s, {e2e['tokens']} tokens, "
        f"{e2e['gaps']} gaps, {e2e['requests']} requests due; "
        f"{window_compiles} compiles in the window; peak "
        f"{peak_bytes} bytes")
    run_rec = RunRecord(cell=cell, cfg=bench_cfg.model, plugin=plugin,
                        mix=mix, window=(t0, t_end), clients=clients,
                        steps=steps, records=records,
                        prefills=[n for t, n in h.prefills
                                  if t0 <= t <= t_end],
                        counters=counters, peaks=peak_table)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {}
    if traced:
        tr = trace_mod.load(trace_dir)
        run_rec.trace = tr
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = tr.window_s
        metrics = spec.read_metrics(cell.per_layer, run_rec, base)
        result["breakdown"] = trace_mod.breakdown(tr)
    else:
        values = dict(e2e, peak_hbm_gib=peak_bytes / 2**30, setup_s=setup_s)
        metrics = {m.name: {"value": float(values[m.name]), "unit": m.unit}
                   for m in cell.end_to_end if values.get(m.name) is not None}
    # the check: after the window and the memory reading, with the
    # program's state freed
    picked = sample_for_check(clients, mix.check, seed)
    served = [h.served(rid) for rid in picked]
    del h, run_rec
    gc.collect()
    arch = plugin.Arch.from_config(bench_cfg.raw)
    t = time.perf_counter()
    readings = yardstick.summarize([
        yardstick.program_readings(plugin, arch, params, s) for s in served])
    judged = sum(s.steps for s in served)
    correct, rows = yardstick.judge(readings, limits)
    correct = correct and judged > 0
    log(f"check: {time.perf_counter() - t:.3f} s")
    lines = [f"judged {len(served)} requests, {judged} decode steps "
             f"(limit: at least 1)"]
    compared = {name for name, _, _ in rows}
    lines.append("not compared: " + ", ".join(
        f"{n} {readings[n]!r}" for n in ("logit_err_max", "route_gap_max",
                                          *yardstick.NUMBERS)
        if n not in compared))
    lines += [f"{name} {value!r} (limit {limit!r})"
              for name, value, limit in rows]
    result = {"correct": bool(correct), "attempted": len(clients),
              "failed": sum(1 for c in clients if not c.stamps),
              "metrics": metrics, "device": device, **result,
              "checks": {"judged_steps": {"value": judged, "limit": 1},
                         **{n: {"value": v, "limit": lim}
                            for n, v, lim in rows}}}
    out = {"result": result, "check_lines": lines}
    if keep_served:
        out.update(served=served, params=params, plugin=plugin, arch=arch)
    return out
