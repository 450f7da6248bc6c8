"""ODMoEEngine — cacheless on-demand MoE decoding (the paper's system).

The engine runs the *full-precision* model layer-by-layer exactly as the
main node does, while a quantized SEP shadow model decodes in lockstep
and supplies multi-layer-lookahead expert predictions.  Expert weights
live in the host ``ExpertStore``; each worker owns one device slot into
which predicted experts are loaded just-in-time and from which they are
promptly evicted after their layer computes (no cache).  Mispredictions
trigger reload events, exactly like the paper's fallback path.

Two entry points share the same decode step:

  * ``generate`` — one fixed batch decoded end-to-end (the paper's
    single-stream experiment driver);
  * ``prefill_request`` + ``decode_batch`` — the request-level API the
    continuous-batching serving loop (``repro.serve``) is built on.
    Per-request caches are kept separate between iterations and joined
    with ``concat_cache_lists`` for each composed step, so requests can
    join and retire between decode iterations (dynamic batch
    membership) while sharing one worker fleet and one expert store.

Everything the timing model needs — who loaded what and when, which
predictions missed, when alignment delayed the shadow — is captured in
the returned ``Trace``.

Correctness invariant (tested): greedy tokens produced by the engine are
bit-identical to the reference ``greedy_generate`` on the same weights,
because expert compute consumes the physically-loaded slot contents and
mispredicted experts are always reloaded before use.  The invariant
holds *by construction*: each wave's expert FFNs run as ONE jitted
grouped call (``repro.kernels.moe_gemm.grouped_topk_contrib`` on the
wave's slot-gathered weight stack) and per-(row, rank) contributions
reduce through the shared fixed-order ``combine_topk`` — the exact
functions the reference ``grouped`` dispatch uses — so engine and
reference consume identical arithmetic.  Composed batches preserve it
per-request: a contribution's value is independent of which wave (or
which batch neighbours) rode along in the grouped call, so batch
membership never changes a request's arithmetic.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_gemm import (combine_topk, grouped_topk_contrib,
                                    grouped_topk_contrib_packed)
from repro.models import prefill
from repro.models.blocks import (block_decode, block_seq, moe_branch,
                                 residual)
from repro.models.config import MOE_FF, NO_FF, ModelConfig
from repro.models.layers import apply_norm
from repro.models.moe import (expert_chunk, grouped_block_contrib, route,
                              shared_expert)
from repro.models.transformer import (at_rep, embed_tokens, layer_params,
                                      logits_from_hidden)
from repro.quant.quantize import shadow_nbytes
from repro.quant.transport import (EXPERT_WEIGHT_NAMES, resolve_policy,
                                   transport_params)

from .align import AlignmentPolicy
from .predictor import (FrequencyPredictor, GateExtrapolator, RandomPredictor,
                        SEPShadow, moe_layer_indices, recall_counts,
                        slice_rollout)
from .specdecode import (_spec_block_step, _spec_mixer_router_step,
                         accept_prefix, select_commit, wave_preds)
from .prefetch import PrefetchExecutor, make_executor, resolve_residency
from .schedule import GroupSchedule
from .spans import joined, span
from .store import ExpertStore, WorkerSlots, stack_shards


@dataclass
class LayerRecord:
    layer: int
    moe_index: int
    group: int
    predicted: Optional[np.ndarray]      # (B,k) or None
    true: np.ndarray                     # (B,k)
    correct: int                         # sum_b |pred_b ∩ true_b|
    reloads: int
    assignments: List[Tuple[int, int]]   # (expert, worker)
    waves: Optional[List[List[Tuple[int, int]]]] = None  # per-wave subsets
    touched: Tuple[int, ...] = ()        # every worker that took a load
    gates: Optional[np.ndarray] = None   # (B,k) gate weights (confidence
    #                                      signal for TieredPolicy calib)
    # residency-aware engines record exactly which predicted experts
    # PHYSICALLY shipped (re-hits excluded); ``None`` keeps the legacy
    # timing model's group-padded predicted-load pricing
    shipped: Optional[Tuple[int, ...]] = None
    rehits: int = 0                      # residency re-hits this layer
    # compute-vs-ship: cold experts whose host-memory streaming beat
    # their worker link, computed on the main node instead of shipped
    # (same round-tripped weights — a scheduling decision, not a model
    # change).  The timing model prices these as serial host compute.
    hosted: Tuple[int, ...] = ()


@dataclass
class TokenRecord:
    index: int
    aligned_token: bool
    aligned_kv: bool
    layers: List[LayerRecord] = field(default_factory=list)
    # speculative verify waves: how many positions the wave carried per
    # request and how many tokens it actually committed (1/1 for the
    # classic one-token step — the timing model prices wave width and
    # benchmarks divide load bytes by COMMITTED tokens, so speculation
    # waste is visible, never hidden)
    spec_len: int = 1
    committed: int = 1


@dataclass
class Trace:
    records: List[TokenRecord] = field(default_factory=list)
    # with ``ODMoEEngine(keep_logits=True)``: each emitted token's
    # (1 or B, V) logits, left on the device, one entry per record row
    # (``repro.core.yardstick`` compares them to a float32 reference)
    logits: List[jax.Array] = field(default_factory=list)

    def recall(self) -> Optional[float]:
        """Overall recall, Eq. (3), over the layers that HAD a
        prediction.  ``None`` (never NaN) when nothing was predicted —
        e.g. ``predictor="none"`` decodes — so aggregation sites can
        skip the value instead of silently poisoning their means."""
        num = den = 0
        for tr in self.records:
            for lr in tr.layers:
                if lr.predicted is None:
                    continue
                num += lr.correct
                den += lr.true.size
        return num / den if den else None

    def recall_per_token(self) -> List[Optional[float]]:
        """recall(n), Eq. (2); ``None`` for tokens with no predicted
        layers (same None-not-NaN contract as :meth:`recall`)."""
        out = []
        for tr in self.records:
            num = sum(lr.correct for lr in tr.layers
                      if lr.predicted is not None)
            den = sum(lr.true.size for lr in tr.layers
                      if lr.predicted is not None)
            out.append(num / den if den else None)
        return out

    def reload_fraction(self) -> float:
        loads = reloads = 0
        for tr in self.records:
            for lr in tr.layers:
                reloads += lr.reloads
                loads += len(lr.assignments)
        return reloads / loads if loads else 0.0


# ---------------------------------------------------- jitted step pieces
# The decode hot path is jit-compiled per (config, layer-kind): one
# dispatch per layer instead of one per primitive.  Factories are
# module-level and lru-cached on the frozen ``ModelConfig``, so every
# engine over the same architecture shares one compiled executable per
# shape — constructing engines stays cheap and the test suite compiles
# each step once, not once per engine.  Parameters enter as pytree
# arguments (never closures), so transport-round-tripped and shadow
# weight sets reuse the same executables too.  A layer's parameters
# enter as its pattern position's stack and its repeat index, sliced
# inside the program: the engine holds the caller's one copy of the
# weights, never a per-layer copy beside it.
def _named(fn, name: str):
    """``fn`` under ``name``: the device trace names a jitted program
    after its function (``jit_<name>``)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@functools.lru_cache(maxsize=None)
def _embed_token(cfg: ModelConfig) -> object:
    return jax.jit(lambda p, t: embed_tokens(cfg, p, t[:, None]))


@functools.lru_cache(maxsize=None)
def _block_step(cfg: ModelConfig, kinds) -> object:
    """Jitted non-MoE block decode (mixer + dense/no FFN)."""
    def fn(stack, r, x, cache, pos):
        return block_decode(cfg, at_rep(stack, r), kinds, x, cache, pos)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _mixer_router_step(cfg: ModelConfig, kinds) -> object:
    """Jitted MoE-layer prefix: mixer + residual (no FFN), post-norm
    router input, the top-k routing decision and the shared expert's
    output — everything between the previous layer and the expert
    waves, fused into one dispatch, named after the mixer
    (``jit_mamba_layer_step`` / ``jit_attn_layer_step`` in a trace).
    The routed expert FFNs run from worker slots (see
    ``_serve_and_compute``); only the gate and the shared expert live
    on the main node."""
    def fn(stack, r, x, cache, pos):
        lp = at_rep(stack, r)
        x, cache, _ = block_decode(cfg, lp, (kinds[0], NO_FF), x, cache,
                                   pos)
        with jax.named_scope("router"):
            h = apply_norm(cfg, x, lp["norm2"])[:, 0]      # router input
            topk_idx, topk_gate, _ = route(cfg, lp["ff"], h)
        return x, cache, h, topk_idx, topk_gate, shared_expert(lp["ff"], h)
    return jax.jit(_named(fn, f"{kinds[0]}_layer_step"))


@functools.lru_cache(maxsize=None)
def _moe_residual(cfg: ModelConfig) -> object:
    """The routed output (f32), the shared expert's and the residual:
    ``x + (routed + shared) * residual_multiplier``."""
    def fn(x, y, shared):
        out = moe_branch(y.astype(x.dtype), shared)
        return residual(cfg, x, out.reshape(x.shape))
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _seq_mixer_router(cfg: ModelConfig, kinds, max_cache_len: int
                      ) -> object:
    """Prefill of one routed layer up to its experts (streamed
    prefill): the mixer over the prompt with its cache, the router's
    top-k over every prompt row and the shared expert's output."""
    def fn(stack, r, x, positions):
        lp = at_rep(stack, r)
        x, _, cache = block_seq(cfg, lp, (kinds[0], NO_FF), x, positions,
                                make_cache=True, max_cache_len=max_cache_len)
        h = apply_norm(cfg, x, lp["norm2"])
        h = h.reshape(-1, h.shape[-1])
        topk_idx, topk_gate, _ = route(cfg, lp["ff"], h)
        return x, cache, h, topk_idx, topk_gate, shared_expert(lp["ff"], h)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _seq_block(cfg: ModelConfig, kinds, max_cache_len: int) -> object:
    """Prefill of one layer without routed experts (streamed prefill)."""
    def fn(stack, r, x, positions):
        x, _, cache = block_seq(cfg, at_rep(stack, r), kinds, x, positions,
                                make_cache=True, max_cache_len=max_cache_len)
        return x, cache
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _last_logits(cfg: ModelConfig) -> object:
    return jax.jit(lambda p, x: logits_from_hidden(cfg, p, x[:, -1:])[:, 0])


@functools.lru_cache(maxsize=None)
def _logits_argmax(cfg: ModelConfig) -> object:
    """Final norm + unembed + greedy pick: ``(token (B,), logits (B, V))``."""
    def fn(p, x):
        with jax.named_scope("logits"):
            logits = logits_from_hidden(cfg, p, x)[:, 0]
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits
    return jax.jit(fn)


# ------------------------------------------------------- batch membership
def concat_cache_lists(cache_lists: Sequence) -> object:
    """Join per-request per-layer caches along the batch axis.

    Dense cache lists concatenate their KV buffers (every request was
    prefilled with the same ``max_cache_len``, so windows agree).
    Paged handles (``repro.serve.kvpool.PagedRequestCache``) compose
    into a batch *view* instead: no KV is copied here — each layer is
    gathered from the pool through the members' page tables when the
    decode step indexes it, and scattered back on assignment.

    An empty batch is a caller bug (the serving loop never composes
    one) and raises ``ValueError``; mixing paged handles and dense
    lists in one batch raises ``TypeError`` — a request is either
    pooled or dense for its whole lifetime.
    """
    if not cache_lists:
        raise ValueError("cannot compose an empty batch of caches")
    first = cache_lists[0]
    paged = [hasattr(c, "compose") for c in cache_lists]
    if any(paged) and not all(paged):
        raise TypeError("cannot mix paged and dense caches in one "
                        "composed batch")
    if paged[0]:                           # paged handles
        return first.compose(cache_lists)
    if len(cache_lists) == 1:
        return list(first)
    return [jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *per_layer)
            for per_layer in zip(*cache_lists)]


def slice_cache_list(cache_list, i: int):
    """Extract request ``i`` from a composed cache list (batch of 1).
    A paged batch returns the member's handle — its pages were already
    committed by the step's scatter, so slicing copies nothing."""
    if hasattr(cache_list, "member"):      # paged batch view
        return cache_list.member(i)
    return [jax.tree.map(lambda a: a[i:i + 1], c) for c in cache_list]


def _without_routed(sub: dict) -> dict:
    """A pattern position's parameters without its routed experts (the
    store holds those)."""
    ff = sub.get("ff")
    if not isinstance(ff, dict) or "router" not in ff:
        return sub
    return dict(sub, ff={k: v for k, v in ff.items()
                         if k not in EXPERT_WEIGHT_NAMES})


def host_held_experts(cfg: ModelConfig, params) -> bool:
    """Whether the routed experts arrive as host ``numpy`` leaves: the
    engine then never puts them on the device."""
    pattern, _ = cfg.pattern()
    return any(isinstance(params["layers"][pos]["ff"][n], np.ndarray)
               for pos, kinds in enumerate(pattern) if kinds[1] == MOE_FF
               for n in EXPERT_WEIGHT_NAMES)


# MoE layers whose predicted experts may be in flight at once: the layer
# being served and the next one.  Deeper windows hold more experts on
# the device beside the slots (PERF.md, Findings).
PEEK_HORIZON = 2


class ODMoEEngine:
    def __init__(self, cfg: ModelConfig, params, *, n_workers: int = 8,
                 group_size: int = 0, predictor: str = "sep",
                 shadow_scheme: str = "int8", lookahead: int = 4,
                 physical_loading: bool = True, seed: int = 0,
                 profiles=None, faults=None, transport=None,
                 wave_compute: str = "grouped", prefetch=None,
                 residency=None, peek_horizon: int = PEEK_HORIZON,
                 speculate: int = 1, sched=None, store=None,
                 gate_stats=None, compute_vs_ship=None,
                 packed_slots: bool = False, keep_logits: bool = False):
        if cfg.is_encoder_decoder:
            raise ValueError("engine drives decoder-only models")
        if wave_compute not in ("grouped", "loop"):
            raise ValueError("wave_compute must be 'grouped' or 'loop'")
        if speculate < 1:
            raise ValueError("speculate must be >= 1")
        if speculate > 1:
            # draft-verify-accept decoding (repro.core.specdecode): the
            # SEP shadow IS the draft model, the verify wave folds S
            # positions into the batch axis of the grouped hot path,
            # and the wave's slots must be distinct within the cache
            # window.  All other predictors have nothing to draft with.
            if predictor != "sep":
                raise ValueError("speculate > 1 requires the SEP shadow "
                                 "(it is the draft model)")
            if wave_compute != "grouped":
                raise ValueError("speculate > 1 requires the grouped "
                                 "wave path")
            from repro.models.config import ATTN
            if any(mixer != ATTN for mixer, _ in cfg.layer_kinds()):
                raise ValueError("speculate > 1 requires all-attention "
                                 "mixers (SSM states cannot fork per "
                                 "wave row)")
            if cfg.sliding_window and cfg.sliding_window < speculate:
                raise ValueError("speculate must fit the sliding window")
        self.speculate = speculate
        if ((prefetch is not None or residency is not None)
                and wave_compute != "grouped"):
            # the retired loop baseline stays the synchronous oracle
            raise ValueError("prefetch/residency require the grouped "
                             "wave path")
        if packed_slots and wave_compute != "grouped":
            # the loop oracle reads full-width slot dicts — it IS the
            # dequantize-on-arrival baseline packed slots are pinned
            # bit-identical against
            raise ValueError("packed_slots requires the grouped wave "
                             "path")
        # True: worker slots keep the wire-format codes+scales resident
        # and the fused Pallas kernel dequantizes in-register — same
        # bits (in-kernel dequant is elementwise-exact), fewer slot
        # bytes and less kernel HBM traffic.
        self.packed_slots = packed_slots
        self.cfg = cfg
        # ``wave_compute='loop'`` keeps the retired per-(row, rank)
        # Python loop as the benchmark baseline and property-test
        # oracle; production decode runs the jit-grouped path.
        self.wave_compute = wave_compute
        # ``transport`` (PrecisionPolicy / scheme name / None=fp32) fixes
        # each expert's on-demand wire precision.  The engine computes
        # with ``transport_params`` — the same round-tripped weights a
        # worker reconstructs on arrival — so decode stays bit-identical
        # to ``greedy_generate(..., transport=...)`` under the SAME
        # policy: precision is part of the model contract, loads only
        # move fewer bytes.
        self.transport = resolve_policy(transport)
        self.moe_layers = moe_layer_indices(cfg)
        self._moe_index = {li: i for i, li in enumerate(self.moe_layers)}
        # routed experts given as host ``numpy`` leaves stay there: the
        # store aliases them, decode loads them into worker slots and
        # prefill streams them a block at a time (``_prefill_streamed``)
        self.host_experts = host_held_experts(cfg, params)
        if self.host_experts and not self.transport.trivial:
            raise ValueError("host-held experts ship at their own "
                             "precision (fp32 transport)")
        # ``compute_vs_ship``: None = always ship (the historical
        # behavior); True / a float enables MoNDE-style per-expert
        # pricing on the reload path — a cold expert whose host-memory
        # streaming time (full weights / cvs GB/s) beats its worker's
        # link time (packed bytes / link GB/s) is computed on the main
        # node instead of shipped.  Pure scheduling: either path runs
        # the same round-tripped weights, so tokens are unchanged.
        if compute_vs_ship is True:
            compute_vs_ship = 42.0        # RTX3090_EDGE.cpu_mem_gbps
        if compute_vs_ship is not None and compute_vs_ship <= 0:
            raise ValueError("compute_vs_ship must be a positive GB/s")
        if compute_vs_ship is not None and wave_compute != "grouped":
            raise ValueError("compute_vs_ship requires the grouped wave "
                             "path")
        self.cvs_gbps = compute_vs_ship
        if sched is not None:
            # a prebuilt (shared) schedule: replicas in a cluster pass
            # the same FleetSchedule so worker-slot contention and
            # liveness are arbitrated through one fleet state
            if profiles is not None:
                raise ValueError("pass profiles via the prebuilt sched")
            self.sched = sched
            n_workers, g = sched.n_workers, sched.group_size
        else:
            g = group_size or max(cfg.top_k, 1)
            if profiles is not None:
                profiles = tuple(profiles)
                n_workers = len(profiles)
                if n_workers % g:
                    raise ValueError("len(profiles) must be divisible by "
                                     "the group size")
            elif n_workers % g:
                n_workers = g * max(1, n_workers // g)
            if (profiles is not None or faults is not None
                    or compute_vs_ship is not None):
                # lazy: repro.fleet imports repro.core.schedule.  cvs
                # needs FleetSchedule's per-link t_load_s pricing, so a
                # uniform fleet (identical ordering — pinned) stands in.
                from repro.fleet import FleetSchedule, uniform_profiles
                self.sched = FleetSchedule(
                    n_workers, g,
                    profiles=profiles or uniform_profiles(n_workers))
            else:
                self.sched = GroupSchedule(n_workers, g)
        self.faults = faults
        # ``gate_stats`` (repro.fleet.placement.GateStatsRecorder, duck-
        # typed) observes every step's true routing — the collection
        # side of gate-statistics placement.  Recording only.
        self.gate_stats = gate_stats
        # the store packs the ORIGINAL weights once; the engine's own
        # compute params unpack those same cached shards, so slot
        # contents and main-node expert weights are bit-identical by
        # construction (and the quantize pass runs once, not twice).
        # A prebuilt ``store`` (cluster replicas share one) must carry
        # the same transport policy or slot contents would diverge from
        # this engine's compute params.
        if store is not None:
            if store.policy is not self.transport and \
                    store.policy.describe() != self.transport.describe():
                raise ValueError("shared store transport policy differs "
                                 "from the engine's")
            self.store = store
        else:
            self.store = ExpertStore(cfg, params, policy=self.transport)
        self.params = (params if self.transport.trivial
                       else transport_params(cfg, params, self.transport,
                                             packed=self.store.get_packed))
        # opportunistic residency + async prefetch (repro.core.prefetch).
        # ``residency=None`` keeps the cacheless rule: release degrades
        # to evict.  ``prefetch=None`` fetches predicted experts ahead on
        # the process's loader pool when loads are physical (bookkeeping
        # loads and the retired loop path fetch inline); ``"sync"`` is
        # the inline oracle.  Either way the commits, the event log and
        # the tokens are the same.
        self.residency = resolve_residency(residency)
        self.slots = WorkerSlots(self.store, n_workers,
                                 physical=physical_loading,
                                 profiles=getattr(self.sched, "profiles",
                                                  None),
                                 residency=self.residency,
                                 packed_resident=packed_slots)
        executor = make_executor(
            prefetch, ahead=physical_loading and wave_compute == "grouped")
        self.prefetch: Optional[PrefetchExecutor] = (
            None if executor is None
            else PrefetchExecutor(self.store, executor,
                                  horizon=peek_horizon,
                                  physical=physical_loading,
                                  packed=packed_slots))
        # each layer's parameters: its pattern position's stack (the
        # caller's arrays, routed experts left to the store) and its
        # repeat index, sliced inside the jitted steps
        pattern, reps = cfg.pattern()
        self._stacks = [_without_routed(sub) for sub in self.params["layers"]]
        rep_idx = [jnp.int32(r) for r in range(reps)]
        self._at = [(self._stacks[li % len(pattern)],
                     rep_idx[li // len(pattern)])
                    for li in range(cfg.num_layers)]
        # the most routed-expert bytes one prefill layer streamed
        self.prefill_stream_peak = 0
        # (rows, V) logits of the latest decode step, left on the device;
        # ``keep_logits`` also keeps every step's in the traces, which
        # holds V floats per token on the device, so only checks ask
        self.last_logits: Optional[jax.Array] = None
        self.keep_logits = keep_logits
        self.predictor_kind = predictor
        self.shadow: Optional[SEPShadow] = None
        self.fly: Optional[GateExtrapolator] = None
        self.freq: Optional[FrequencyPredictor] = None
        self.rand: Optional[RandomPredictor] = None
        if predictor == "sep":
            self.shadow = SEPShadow(cfg, params, shadow_scheme)
        elif predictor in ("nextgate", "multigate"):
            routers = self.store.router_weights(params)
            la = 1 if predictor == "nextgate" else lookahead
            self.fly = GateExtrapolator(cfg, routers, la)
        elif predictor == "freq":
            self.freq = FrequencyPredictor(cfg)
        elif predictor == "random":
            self.rand = RandomPredictor(cfg, seed)
        elif predictor != "none":
            raise ValueError(f"unknown predictor {predictor!r}")

    # -------------------------------------------------------------- caches
    def _unstack(self, caches):
        pattern, reps = self.cfg.pattern()
        out = []
        for li in range(self.cfg.num_layers):
            pos, r = li % len(pattern), li // len(pattern)
            out.append(jax.tree.map(lambda a: a[r], caches[pos]))
        return out

    def _stack(self, cache_list):
        pattern, reps = self.cfg.pattern()
        out = []
        for pos in range(len(pattern)):
            per_rep = [cache_list[r * len(pattern) + pos] for r in range(reps)]
            out.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
        return tuple(out)

    # ----------------------------------------------------------- requests
    def prefill_request(self, batch, max_cache_len: int, *,
                        kv_pool=None, rid: Optional[int] = None):
        """Prefill one request (or fixed batch) on the main node.

        Returns ``(first_token (B,), cache_list, pos (B,))`` — the
        per-request decode state the serving loop carries between
        composed iterations.  The first generated token falls out of
        prefill, so a request's TTFT is admission wait + prefill time.

        With ``kv_pool`` (a ``repro.serve.kvpool.KVPool``) the prefilled
        KV is adopted into pool pages and ``cache_list`` is the paged
        stand-in instead of dense buffers: the dense prefill output is
        transient, and the request's steady-state KV charge becomes its
        page-table allocation against the pool budget.  The caller must
        have reserved ``pages_for(prompt_len)`` pages (admission
        control) and supplies the request id the page table is keyed by.
        """
        with span("prefill", prompt_len=int(batch["tokens"].shape[1])):
            if self.host_experts:
                logits, cache_list = self._prefill_streamed(batch,
                                                            max_cache_len)
                state = {"pos": jnp.full(batch["tokens"].shape[:1],
                                         batch["tokens"].shape[1],
                                         jnp.int32)}
            else:
                logits, state = prefill(self.cfg, self.params, batch,
                                        max_cache_len, moe_method="grouped")
                cache_list = self._unstack(state["caches"])
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if kv_pool is not None:
                if batch["tokens"].shape[0] != 1 or rid is None:
                    raise ValueError("paged prefill adopts one request "
                                     "(B=1) with its request id")
                cache_list = kv_pool.adopt(rid, cache_list,
                                           batch["tokens"].shape[1])
            return token, cache_list, state["pos"]

    def _prefill_streamed(self, batch, max_cache_len: int):
        """Prefill with the routed experts held on the host: layer by
        layer, each routed layer's experts streamed from the store a
        block at a time (the blocks its prompt routes to), computed with
        the grouped expert GEMM and dropped after the layer.  Returns
        ``(last-token logits, per-layer caches)``."""
        cfg = self.cfg
        tokens = jnp.asarray(batch["tokens"])
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x = embed_tokens(cfg, self.params, tokens)
        g = expert_chunk(cfg.num_experts)
        cache_list = []
        for li, kinds in enumerate(cfg.layer_kinds()):
            stack, r = self._at[li]
            if kinds[1] != MOE_FF:
                x, cache = _seq_block(cfg, kinds, max_cache_len)(
                    stack, r, x, positions)
                cache_list.append(cache)
                continue
            x, cache, h, topk_idx, topk_gate, shared = _seq_mixer_router(
                cfg, kinds, max_cache_len)(stack, r, x, positions)
            cache_list.append(cache)
            routed = np.unique(np.asarray(topk_idx))
            blocks = sorted({int(e) // g * g for e in routed})
            nbytes = len(blocks) * g * self.store.expert_bytes
            self.prefill_stream_peak = max(self.prefill_stream_peak, nbytes)
            with span("prefill_stream", layer=li, experts=int(routed.size),
                      nbytes=nbytes):
                contrib = None
                for lo in blocks:
                    block = self.store.stream_block(li, lo, g)
                    c = grouped_block_contrib(
                        h, [block[n] for n in EXPERT_WEIGHT_NAMES], lo,
                        topk_idx, topk_gate)
                    contrib = c if contrib is None else contrib + c
                x = _moe_residual(cfg)(x, combine_topk(contrib), shared)
        return _last_logits(cfg)(self.params, x), cache_list

    # ------------------------------------------------------------ generate
    def generate(self, batch, num_tokens: int,
                 policy: AlignmentPolicy = AlignmentPolicy(1, 1)):
        """End-to-end greedy generation.  ``speculate=1`` decodes one
        token per step; ``speculate=k`` decodes in draft-verify-accept
        waves (``repro.core.specdecode``) — same tokens, fewer steps."""
        if self.speculate > 1:
            return self._generate_spec(batch, num_tokens, policy)
        cfg = self.cfg
        prompt_len = batch["tokens"].shape[1]
        max_cache_len = prompt_len + num_tokens + 2
        main_token, cache_list, pos = self.prefill_request(
            batch, max_cache_len)
        if self.shadow is not None:
            self.shadow.reset(batch, max_cache_len)
        tokens_out = [main_token]
        trace = Trace()
        for n in range(1, num_tokens):
            preds: Dict[int, np.ndarray] = {}
            at = ak = False
            if self.shadow is not None:
                at = policy.align_token_at(n)
                ak = policy.align_kv_at(n)
                if ak:
                    self.shadow.align_kv(
                        {"caches": self._stack(cache_list), "pos": pos})
                shadow_in = main_token if at else self.shadow.token
                preds = self.shadow.step(shadow_in)
            rec = TokenRecord(index=n, aligned_token=at, aligned_kv=ak)
            main_token, cache_list, pos = self.decode_batch(
                main_token, cache_list, pos, preds, n, rec)
            tokens_out.append(main_token)
            trace.records.append(rec)
            if self.keep_logits:
                trace.logits.append(self.last_logits)
        return jnp.stack(tokens_out, axis=1), trace

    def _generate_spec(self, batch, num_tokens: int,
                       policy: AlignmentPolicy):
        """Speculative generation: the shadow drafts ``speculate``
        tokens per wave, one verify wave commits the accepted prefix.
        Tokens are bit-identical to the one-token loop (and therefore
        to ``greedy_generate``) by the specdecode prefix argument; the
        batch commits in lockstep (the minimum accepted prefix across
        rows) so ``pos`` stays uniform, matching the fixed-batch
        semantics of :meth:`generate`.  The alignment policy and fault
        scripts see wave-start token indices as their step index —
        speculation compresses steps, so index ``n`` means "the wave
        that begins at generated token ``n``"."""
        prompt_len = batch["tokens"].shape[1]
        max_cache_len = prompt_len + num_tokens + 2 + self.speculate
        main_token, cache_list, pos = self.prefill_request(
            batch, max_cache_len)
        self.shadow.reset(batch, max_cache_len)
        tokens_out = [main_token]
        trace = Trace()
        n = 1
        while n < num_tokens:
            s_w = min(self.speculate, num_tokens - n)
            at = policy.align_token_at(n)
            ak = policy.align_kv_at(n)
            if ak:
                self.shadow.align_kv(
                    {"caches": self._stack(cache_list), "pos": pos})
            first = main_token if at else self.shadow.token
            st0 = dict(self.shadow.state, token=self.shadow.token)
            # fused drafting: one scan dispatch for the whole rollout
            # (arithmetic identical to chained step_state calls —
            # repro.core.specdecode.shadow_rollout is the serial
            # spelling the property tests pin it against)
            drafts, preds_steps, roll = self.shadow.rollout_states(
                st0, first, s_w)
            wave_in = jnp.concatenate(
                [main_token[:, None], drafts.astype(jnp.int32)], axis=1)
            rec = TokenRecord(index=n, aligned_token=at, aligned_kv=ak,
                              spec_len=s_w)
            verified, c, cache_list, pos = self.decode_batch_spec(
                wave_in, cache_list, pos, wave_preds(preds_steps), n, rec,
                lockstep=True)
            ci = int(c[0])               # lockstep: uniform across rows
            trace.records.append(rec)
            for s in range(ci):
                tokens_out.append(verified[:, s])
            main_token = verified[:, ci - 1]
            # roll the shadow back to the accepted prefix: step ci-1
            # consumed exactly [first, true tokens 0..ci-2] — rejected
            # drafts never entered the surviving shadow KV
            st = slice_rollout(roll, ci - 1)
            self.shadow.token = st["token"]
            self.shadow.state = {"caches": st["caches"], "pos": st["pos"]}
            n += ci
        return jnp.stack(tokens_out, axis=1), trace

    # ---------------------------------------------------------- one token
    def decode_batch(self, token, cache_list, pos, preds, step_idx,
                     rec: TokenRecord):
        """One decode iteration for the (possibly composed) batch.

        ``token``/``pos`` are (B,); ``cache_list`` is per-layer with
        batch axis B — either dense buffers or a paged batch view
        (``repro.serve.kvpool``): indexing a layer gathers the members'
        KV pages into the same dense ``(B, W, ...)`` buffer, and the
        assignment after ``block_decode`` scatters the written slot
        back through the page tables, so compute is bit-identical
        either way.  ``preds`` maps layer -> (B,k) predicted experts
        for THIS iteration (rows in batch order).  Rows are arithmetically
        independent, so the serving loop may change batch membership
        freely between calls.  Appends per-layer records to ``rec``.

        Scripted faults fire here: step-scoped events before anything
        computes, layer-scoped ones inside ``_serve_and_compute`` (the
        stranded-predicted-load window).  A worker death costs at most
        the reloads for what it held — never the tokens.

        Every main-node segment between expert waves runs as one jitted
        dispatch (``_block_step`` / ``_mixer_router_step`` /
        ``_logits_argmax``); only scheduling, loading and the trace
        stay in Python.  ``wave_compute='loop'`` instead replays the
        retired pre-refactor path — eager per-primitive blocks plus the
        per-(row, rank) expert loop — as the wall-clock baseline,
        producing bit-identical tokens by the shared-arithmetic
        contract.
        """
        with span("decode_step", step=step_idx, rows=int(token.shape[0]),
                  rids=joined(self.slots.request_context)):
            if self.wave_compute == "loop":
                return self._decode_batch_loop(token, cache_list, pos,
                                               preds, step_idx, rec)
            cfg = self.cfg
            if self.faults is not None:
                self.faults.apply(step_idx, self.sched.state, self.slots)
            x = _embed_token(cfg)(self.params, token)
            pending: Dict[int, np.ndarray] = dict(preds)
            # SEP predictions cover the whole token up front: queue the
            # first layers' fetches NOW so transfers overlap the compute
            # before each layer's wave boundary; each layer served queues
            # the next (the peek horizon bounds the window)
            if self.prefetch is not None and pending:
                self.prefetch.enqueue(step_idx, -1, pending,
                                      select=self._fetch_ahead)
            moe_i = -1
            for li, kinds in enumerate(cfg.layer_kinds()):
                stack, r = self._at[li]
                if kinds[1] != MOE_FF:
                    x, cache_list[li], _ = _block_step(cfg, kinds)(
                        stack, r, x, cache_list[li], pos)
                    continue
                moe_i += 1
                # mixer + residual + router input + gate + shared expert,
                # one dispatch
                x, cache_list[li], h, topk_idx, topk_gate, shared = \
                    _mixer_router_step(cfg, kinds)(stack, r, x,
                                                   cache_list[li], pos)
                with span("router_sync", layer=li):
                    true = np.asarray(topk_idx)
                x = self._moe_bookkeeping(step_idx, li, moe_i, pending,
                                          true, h, topk_gate, shared, x,
                                          rec)
            if self.prefetch is not None:
                self.prefetch.finish_token(step_idx)
            token, self.last_logits = _logits_argmax(cfg)(self.params, x)
            return token, cache_list, pos + 1

    # ------------------------------------------------------- verify wave
    def decode_batch_spec(self, tokens, cache_list, pos, preds, step_idx,
                          rec: TokenRecord, *, max_commit=None,
                          lockstep: bool = False):
        """One draft-verify-accept wave for the (possibly composed)
        batch — see ``repro.core.specdecode`` for the arithmetic
        contract.

        ``tokens``: (B, S) wave inputs — column 0 each request's true
        last committed token, columns 1.. the shadow's drafts;
        ``preds``: {layer -> (B*S, k)} in wave-row order (row ``b*S+s``
        = request ``b``, position ``s``).  Expert serving treats the
        wave as a (B*S)-row batch through the unchanged
        ``_moe_bookkeeping`` machinery, so loads, faults, prefetch and
        residency behave exactly as for a composed batch of that size.

        Returns ``(verified (B, S), c (B,), cache_list, pos + c)``:
        request ``b`` committed ``verified[b, :c_b]``.  ``max_commit``
        (B,) caps per-request commits (serving token budgets);
        ``lockstep=True`` commits the batch minimum everywhere (fixed-
        batch generate).  ``S == 1`` delegates to the classic
        one-token step — bit-identical by shared code."""
        cfg = self.cfg
        b, s_w = tokens.shape
        if s_w == 1:
            tok, cache_list, pos = self.decode_batch(
                tokens[:, 0], cache_list, pos, preds, step_idx, rec)
            rec.spec_len, rec.committed = 1, b   # uniform accounting
            return (tok[:, None], jnp.ones((b,), jnp.int32), cache_list,
                    pos)
        with span("decode_step", step=step_idx, rows=b * s_w,
                  rids=joined(self.slots.request_context)):
            if self.faults is not None:
                self.faults.apply(step_idx, self.sched.state, self.slots)
            x = _embed_token(cfg)(self.params, tokens.reshape(-1))
            pos_rows = (pos[:, None]
                        + jnp.arange(s_w, dtype=pos.dtype)).reshape(-1)
            pending: Dict[int, np.ndarray] = dict(preds)
            if self.prefetch is not None and pending:
                self.prefetch.enqueue(step_idx, -1, pending,
                                      select=self._fetch_ahead)
            spec_caches: Dict[int, dict] = {}
            moe_i = -1
            for li, kinds in enumerate(cfg.layer_kinds()):
                stack, r = self._at[li]
                # each wave row verifies against its own copy of the
                # request's cache (seeded with the earlier rows' K/V
                # inside the spec step); the commit below SELECTS the
                # accepted row, so nothing is written back until
                # acceptance
                repl = jax.tree.map(lambda a: jnp.repeat(a, s_w, axis=0),
                                    cache_list[li])
                if kinds[1] != MOE_FF:
                    x, spec_caches[li] = _spec_block_step(cfg, kinds, s_w)(
                        stack, r, x, repl, pos_rows)
                    continue
                moe_i += 1
                x, spec_caches[li], h, topk_idx, topk_gate, shared = \
                    _spec_mixer_router_step(cfg, kinds, s_w)(
                        stack, r, x, repl, pos_rows)
                with span("router_sync", layer=li):
                    true = np.asarray(topk_idx)
                x = self._moe_bookkeeping(step_idx, li, moe_i, pending,
                                          true, h, topk_gate, shared, x,
                                          rec)
            if self.prefetch is not None:
                self.prefetch.finish_token(step_idx)
            verified, self.last_logits = _logits_argmax(cfg)(self.params, x)
            verified = verified.reshape(b, s_w)
            c = accept_prefix(tokens, verified)
            if max_commit is not None:
                c = jnp.minimum(c, jnp.asarray(max_commit, jnp.int32))
            if lockstep:
                c = jnp.full_like(c, jnp.min(c))
            for li in range(cfg.num_layers):
                cache_list[li] = select_commit(spec_caches[li], c, s_w)
            rec.spec_len = s_w
            rec.committed = int(jnp.sum(c))
            return verified, c, cache_list, pos + c

    def _fetch_ahead(self, layer: int, experts: List[int]) -> List[int]:
        """The predicted experts of ``layer`` worth fetching ahead: those
        ``_serve_and_compute`` will place onto load slots, as the fleet
        stands now.  Under residency an expert still resident re-hits
        in place and holds its slot; the overflow beyond the fleet's
        slots reloads on demand, so fetched ahead it would only sit on
        the device until the token ends."""
        rest: List[int] = []
        reserved: Dict[int, int] = {}
        for e in experts:
            w = (self.slots.worker_with(layer, e)
                 if self.residency is not None else None)
            if w is None:
                rest.append(e)
            else:
                reserved[w] = reserved.get(w, 0) + 1
        return [e for e, _ in self.sched.place(self._moe_index[layer], rest,
                                               reserved)]

    def _moe_bookkeeping(self, step_idx, li, moe_i, pending, true, h,
                         topk_gate, shared, x, rec: TokenRecord):
        """Everything around one MoE layer's expert waves, shared by the
        production and the retired decode paths: on-the-fly predictors,
        serve + compute, the shared expert and the residual, trace
        recording and the cacheless eviction rule (or, under residency,
        the opportunistic release)."""
        with span("serve", layer=li, experts=int(np.unique(true).size)):
            b = true.shape[0]
            # on-the-fly predictors key off the router input
            if self.fly is not None:
                for tgt, p in self.fly.predict_from(li, h).items():
                    pending[tgt] = p
            if self.freq is not None:
                pending[li] = self.freq.predict(li, b)
            if self.rand is not None:
                pending[li] = self.rand.predict(li, b)
            if self.prefetch is not None and pending:
                # slide the window one layer on; on-the-fly predictors
                # only just produced this layer's (and lookahead)
                # predictions: queue whatever is new in-window
                self.prefetch.enqueue(step_idx, li, pending,
                                      select=self._fetch_ahead)
            pred = pending.get(li)
            lr, y = self._serve_and_compute(
                step_idx, li, moe_i, pred, true, h, np.asarray(topk_gate))
            rec.layers.append(lr)
            if self.freq is not None:
                self.freq.observe(li, true)
            if self.gate_stats is not None:
                # realized routing feeds the placement optimizer (recording
                # only — scheduling for THIS run is untouched)
                self.gate_stats.observe(moe_i, true, np.asarray(topk_gate))
            if self.residency is not None:
                # realized routing feeds the gate-statistics policy
                self.slots.observe_gates(li, true, np.asarray(topk_gate))
            x = _moe_residual(self.cfg)(x, y, shared)
            # prompt eviction — cacheless rule.  Every worker that took a
            # load this layer (predicted or reload, group or spill) drops
            # its experts, so a mispredicted never-used resident cannot
            # linger to fake a later hit.  Under opportunistic residency the
            # drop becomes a *release*: residents keep their free slots and
            # a later load of the same expert re-hits instead of reloading.
            used = set(lr.touched)
            used.update(w for _, w in lr.assignments)
            used.update(self.sched.workers_of_group(lr.group))
            for w in sorted(used):
                if self.residency is not None:
                    self.slots.release(w)
                else:
                    self.slots.evict(w)
            return x

    # ------------------------------------------- retired loop baseline
    def _decode_batch_loop(self, token, cache_list, pos, preds, step_idx,
                           rec: TokenRecord):
        """The pre-refactor decode step, kept verbatim as the
        ``wave_compute='loop'`` baseline: per-primitive eager block
        compute, per-step parameter re-slicing, and the per-(row, rank)
        Python expert loop (``_compute_wave_loop``).  The wall-clock
        benchmark measures the grouped path against this; the property
        suite pins both token-bit-identical.  Never used in production
        decode."""
        cfg = self.cfg
        if self.faults is not None:
            self.faults.apply(step_idx, self.sched.state, self.slots)
        x = embed_tokens(cfg, self.params, token[:, None])
        pending: Dict[int, np.ndarray] = dict(preds)
        moe_i = -1
        for li, kinds in enumerate(cfg.layer_kinds()):
            lp = layer_params(cfg, self.params, li)
            if kinds[1] != MOE_FF:
                x, cache_list[li], _ = block_decode(
                    cfg, lp, kinds, x, cache_list[li], pos)
                continue
            moe_i += 1
            # mixer + residual (no FFN yet)
            x, cache_list[li], _ = block_decode(
                cfg, lp, (kinds[0], NO_FF), x, cache_list[li], pos)
            h = apply_norm(cfg, x, lp["norm2"])[:, 0]          # router input
            topk_idx, topk_gate, _ = route(cfg, lp["ff"], h)
            true = np.asarray(topk_idx)
            x = self._moe_bookkeeping(step_idx, li, moe_i, pending, true,
                                      h, topk_gate,
                                      shared_expert(lp["ff"], h), x, rec)
        logits = logits_from_hidden(cfg, self.params, x)[:, 0]
        self.last_logits = logits
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache_list,
                pos + 1)

    # ------------------------------------------------------ serve+compute
    def _serve_and_compute(self, step_idx, layer, moe_i, pred, true, h,
                           gates) -> Tuple[LayerRecord, jax.Array]:
        """Load the routed experts and compute their FFNs from worker
        slots, in *waves* when the composed batch needs more unique
        experts than the fleet holds at once (each wave assigns distinct
        workers; later waves overwrite earlier slots, which the timing
        model sees as serialized loads on busy workers).

        Each wave is ONE jitted grouped-FFN call on the wave's
        slot-gathered weight stack; per-(row, rank) contributions land
        in a ``(B, k, d)`` buffer and reduce through the shared
        fixed-rank-order ``combine_topk``, independent of wave
        membership, so a request's output is bit-identical however the
        batch was composed — and identical to the reference ``grouped``
        dispatch, which calls the same primitives.
        """
        group = self.sched.group_of(moe_i)
        touched: set = set()
        rehits = 0
        shipped: List[int] = []
        # 1) predicted experts were loaded ahead of time.  A composed
        # batch can predict more unique experts than the group holds;
        # those spread onto the other groups' idle workers and onto
        # spare slots of multi-slot workers (the whole fleet serves the
        # batch).  Predictions beyond the fleet's slot count cannot be
        # held anywhere and fall through to the reload path.
        #
        # Under residency, predicted experts still resident anywhere
        # re-hit in place first (no load, no bytes); the rest commit in
        # the same deterministic expert order onto the remaining load
        # targets, consuming prefetched payloads when the executor
        # finished them in time.  All scheduling decisions happen HERE,
        # on the main thread — an async executor can only change when
        # payload bytes were fetched, never who serves what.
        if pred is not None:
            pred_experts = list(dict.fromkeys(int(e) for e in pred.reshape(-1)))
            rest: List[int] = []
            reserved: Dict[int, int] = {}
            if self.residency is not None:
                for e in pred_experts:
                    w = self.slots.reactivate(layer, e)
                    if w is None:
                        rest.append(e)
                    else:                      # re-hit: slot already live
                        rehits += 1
                        touched.add(w)
                        reserved[w] = reserved.get(w, 0) + 1
            else:
                rest = pred_experts
            # the schedule places predicted experts onto load slots
            # (skipping slots pledged to re-hits); a placement plan's
            # expert->worker affinity is honored here, overflow beyond
            # the fleet's slots falls through to the reload path
            pairs = self.sched.place(moe_i, rest, reserved)
            payloads = (self.prefetch.collect(
                step_idx, layer, [e for e, _ in pairs])
                if self.prefetch is not None and pairs else {})
            for e, w in pairs:
                if self.slots.load(step_idx, layer, e, w, predicted=True,
                                   payload=payloads.get(e)):
                    shipped.append(e)
                touched.add(w)
        # mid-step faults: a worker dying HERE strands the predicted
        # experts it just loaded — the gate pass below reloads them on a
        # surviving worker (the paper's degraded-but-correct fallback)
        if self.faults is not None:
            self.faults.apply_layer(step_idx, moe_i, self.sched.state,
                                    self.slots)
        # 2) gate result is ground truth: reload anything missing
        order = self.sched.serving_order(moe_i)    # alive workers only
        needed = list(dict.fromkeys(int(e) for e in true.reshape(-1)))
        reloads = 0
        assignments: List[Tuple[int, int]] = []
        waves: List[List[Tuple[int, int]]] = []
        hosted: List[int] = []
        contrib = None                     # grouped: (B, k, d) fp32
        loop_contrib: Dict[Tuple[int, int], jax.Array] = {}
        remaining = needed
        while remaining:
            # workers already serving a *correct* prediction are claimed;
            # a multi-slot worker computes one expert per wave
            wave: Dict[int, int] = {}
            claimed: set = set()
            for e in remaining:
                w = self.slots.worker_with(layer, e)
                if w is not None and w not in claimed:
                    if (self.residency is not None
                            and self.slots.claim_resident(layer, e, w)):
                        rehits += 1     # mispredicted but still resident
                        touched.add(w)
                    wave[e] = w
                    claimed.add(w)
            free = [w for w in order if w not in claimed]
            if not wave and not free:
                raise RuntimeError(
                    f"no alive workers left to serve layer {layer}")
            # dry-assign the wave's misses first, then fetch them as one
            # batch through the executor (concurrent transfers), then
            # commit in assignment order — the same worker choices and
            # event order the synchronous path produces
            loads: List[Tuple[int, int]] = []
            wave_hosted: List[int] = []
            for e in remaining:
                if e in wave:
                    continue
                if self.slots.worker_with(layer, e) is not None:
                    continue   # resident on a busy multi-slot worker:
                    #            computes next wave, no reload needed
                if not free:
                    break                          # overflow -> next wave
                # compute-vs-ship (MoNDE-style): if streaming this
                # expert from host memory beats its candidate worker's
                # link, compute it on the main node — no load, no slot,
                # no reload; the candidate slot stays free for the next
                # miss.  Same round-tripped weights either way.
                if self._prefer_host(layer, e, free[0]):
                    wave_hosted.append(e)
                    continue
                loads.append((e, free.pop(0)))
            payloads = (self.prefetch.fetch_now(step_idx, layer,
                                                [e for e, _ in loads])
                        if self.prefetch is not None and loads else {})
            for e, w in loads:
                self.slots.load(step_idx, layer, e, w, predicted=False,
                                payload=payloads.get(e))
                touched.add(w)
                reloads += 1
                wave[e] = w
            if self.wave_compute == "loop":
                self._compute_wave_loop(layer, h, true, gates, wave,
                                        loop_contrib)
            else:
                if wave:           # all-hosted waves skip the slot call
                    contrib = self._compute_wave(layer, h, true, gates,
                                                 wave, contrib)
                if wave_hosted:
                    contrib = self._compute_hosted(layer, h, true, gates,
                                                   wave_hosted, contrib)
            done = [(e, wave[e]) for e in remaining if e in wave]
            assignments.extend(done)
            waves.append(done)
            hosted.extend(wave_hosted)
            skip = set(wave) | set(wave_hosted)
            remaining = [e for e in remaining if e not in skip]
        # deterministic accumulation: (row, rank) order, wave-independent
        if self.wave_compute == "loop":
            y = jnp.zeros((true.shape[0], h.shape[1]), jnp.float32)
            for bi in range(true.shape[0]):
                for j in range(true.shape[1]):
                    y = y.at[bi].add(loop_contrib[(bi, j)])
        else:
            y = combine_topk(contrib)
        correct = recall_counts(pred, true) if pred is not None else 0
        lr = LayerRecord(layer=layer, moe_index=moe_i, group=group,
                         predicted=pred, true=true, correct=correct,
                         reloads=reloads, assignments=assignments,
                         waves=waves, touched=tuple(sorted(touched)),
                         gates=gates,
                         shipped=(tuple(shipped)
                                  if self.residency is not None else None),
                         rehits=rehits, hosted=tuple(hosted))
        return lr, y

    # ------------------------------------------------- compute-vs-ship
    def _prefer_host(self, layer: int, expert: int, worker: int) -> bool:
        """Price a cold expert both ways: ship its packed payload over
        the candidate worker's (possibly throttled) link, or stream the
        full-width weights from host memory and compute on the main
        node.  ``FleetSchedule.t_load_s`` is the same pricing the timing
        clock uses, so the decision can never desynchronize from the
        replayed cost."""
        if self.cvs_gbps is None:
            return False
        t_ship = self.sched.t_load_s(worker,
                                     self.store.packed_bytes(layer, expert))
        t_host = self.store.expert_bytes / (self.cvs_gbps * 1e9)
        return t_host < t_ship

    def _compute_hosted(self, layer, h, true, gates, experts: List[int],
                        contrib):
        """Main-node twin of ``_compute_wave``: the stacked weights come
        straight from the store's packed shards (``unpack_shard`` — the
        identical round-trip worker slots hold) instead of slot
        contents, so the grouped-FFN call produces bit-identical
        contributions and the (B, k, d) accumulation stays order-free."""
        with span("wave", layer=layer, experts=len(experts)):
            experts = sorted(experts)
            stacked = stack_shards(
                [self.store.unpack_shard(layer, e) for e in experts])
            eid = np.asarray(experts)
            match = true[..., None] == eid
            slot_map = np.where(match.any(-1), match.argmax(-1),
                                -1).astype(np.int32)
            wc = grouped_topk_contrib(h, stacked["w_gate"], stacked["w_up"],
                                      stacked["w_down"], jnp.asarray(slot_map),
                                      jnp.asarray(gates))
            return wc if contrib is None else contrib + wc

    def _compute_wave(self, layer, h, true, gates, wave: Dict[int, int],
                      contrib):
        """One jitted grouped-FFN call for this wave: gather the wave's
        resident slot weights as a stacked ``(E_wave, d, f)`` tensor,
        map every (row, rank) pair routed to a wave expert onto the
        stacked axis, and add the gate-weighted contributions into the
        ``(B, k, d)`` accumulator (masked pairs contribute exact
        zeros, so cross-wave accumulation is order-free)."""
        with span("wave", layer=layer, experts=len(wave)):
            if self.packed_slots:
                # packed-resident slots: one fused in-kernel-dequant grouped
                # call per resident scheme group.  Pairs routed to another
                # group's experts are masked to exact zeros, so the
                # per-scheme split is just more wave partitioning — the
                # accumulation stays order-free and bit-identical.
                _, groups = self.slots.gather_stack_packed(layer, wave)
                wc = None
                for scheme, eids, parts in groups:
                    eid = np.asarray(eids)
                    match = true[..., None] == eid
                    slot_map = np.where(match.any(-1), match.argmax(-1),
                                        -1).astype(np.int32)
                    gc = grouped_topk_contrib_packed(
                        h, parts, jnp.asarray(slot_map), jnp.asarray(gates),
                        scheme=scheme)
                    wc = gc if wc is None else wc + gc
                return wc if contrib is None else contrib + wc
            experts, stacked = self.slots.gather_stack(layer, wave)
            eid = np.asarray(experts)
            match = true[..., None] == eid               # (B, k, E_wave)
            slot_map = np.where(match.any(-1), match.argmax(-1),
                                -1).astype(np.int32)
            wc = grouped_topk_contrib(h, stacked["w_gate"], stacked["w_up"],
                                      stacked["w_down"], jnp.asarray(slot_map),
                                      jnp.asarray(gates))
            return wc if contrib is None else contrib + wc

    def _compute_wave_loop(self, layer, h, true, gates,
                           wave: Dict[int, int], contrib):
        """The retired per-(row, rank) Python loop — kept verbatim as
        the ``wave_compute='loop'`` baseline the wall-clock benchmark
        measures against and the property suite pins the grouped path
        bit-identical to.  Not used by production decode."""
        for bi in range(true.shape[0]):
            hb = h[bi].astype(jnp.float32)
            for j in range(true.shape[1]):
                e = int(true[bi, j])
                if e not in wave:
                    continue
                w = wave[e]
                wd = self.slots.slot(w, layer, e)   # asserts residency
                out = (jax.nn.silu(hb @ wd["w_gate"]) * (hb @ wd["w_up"])
                       ) @ wd["w_down"]
                contrib[(bi, j)] = float(gates[bi, j]) * out

    # ---------------------------------------------------- prefetch report
    def prefetch_report(self) -> dict:
        """Prefetch/residency effectiveness counters: what the executor
        fetched ahead vs inline, and what residency re-hits saved.
        ``rehit_rate`` is re-hits over all slot fills (loads + re-hits)
        — the fraction of expert placements that moved zero bytes."""
        rs = self.slots.residency_stats
        loads = self.slots.stats["loads"]
        denom = loads + rs["rehits"]
        rep = {
            "residency": getattr(self.residency, "name", None),
            "rehit_rate": rs["rehits"] / denom if denom else 0.0,
            "bytes_moved": self.slots.bytes_moved,
        }
        rep.update({f"residency_{k}": v for k, v in rs.items()})
        if self.prefetch is not None:
            rep["executor"] = self.prefetch.executor.kind
            rep.update({f"prefetch_{k}": v
                        for k, v in self.prefetch.stats.items()})
        return rep

    def close(self) -> None:
        """Shut down the prefetch executor's worker threads (no-op for
        synchronous engines)."""
        if self.prefetch is not None:
            self.prefetch.close()

    # ------------------------------------------------------------- memory
    def device_bytes(self, caches=()) -> dict:
        """Bytes the device holds now, by holder: the non-expert
        weights, routed-expert arrays of the weights (0 when the experts
        are host-held), the SEP shadow, the worker slots' residents and
        ``caches`` (any pytree of live request state), with the most
        routed-expert bytes one streamed prefill layer moved."""
        def dev(tree):
            return sum(int(a.size) * a.dtype.itemsize
                       for a in jax.tree.leaves(tree)
                       if isinstance(a, jax.Array))
        pattern, _ = self.cfg.pattern()
        routed = [self.params["layers"][pos]["ff"][n]
                  for pos, kinds in enumerate(pattern) if kinds[1] == MOE_FF
                  for n in EXPERT_WEIGHT_NAMES]
        top = {k: v for k, v in self.params.items() if k != "layers"}
        return {"non_expert": dev(top) + dev(self._stacks),
                "routed_experts": dev(routed),
                "shadow": dev(self.shadow.params) if self.shadow else 0,
                "worker_slots": self.slots.resident_device_bytes(),
                "caches": dev(caches),
                "prefill_stream_peak": self.prefill_stream_peak}

    def memory_report(self, caches=()) -> dict:
        """Bytes by node type — the paper's Table 2 part (ii) quantities
        — and, under ``device``, what the device holds now
        (:meth:`device_bytes` of ``caches``)."""
        def nbytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree))
        total = nbytes(self.params)
        n_moe = len(self.moe_layers)
        expert_total = n_moe * self.cfg.num_experts * self.store.expert_bytes
        main = total - expert_total
        shadow = 0
        if self.shadow is not None:
            # exact deployed footprint of the shadow's parameter tree:
            # quantized leaves at packed size (codes + scales), the
            # leaves that stay full width (norms, small vectors) at
            # their real nbytes — not a flat fraction of the model
            shadow = shadow_nbytes(self.shadow.params, self.shadow.scheme)
        # peak, not steady-state: while a non-fp32 shard dequantizes on
        # arrival the packed wire buffer and the full-width slot are
        # both live on the worker (see WorkerSlots.transient_packed_bytes)
        transient = self.slots.transient_packed_bytes()
        fleet_bytes = (sum(self.slots.capacity)
                       * self.slots.slot_unit_bytes()
                       + self.sched.n_workers * transient)
        transport_max = max(
            (self.store.packed_bytes(li, e) for li in self.moe_layers
             for e in range(self.cfg.num_experts)), default=0)
        return {
            "main_node_bytes": main,
            "per_worker_bytes": self.slots.device_bytes_per_worker(),
            "n_workers": self.sched.n_workers,
            "shadow_node_bytes": shadow,
            "total_bytes": main + shadow + fleet_bytes,
            "fully_cached_bytes": total,
            # largest per-expert wire payload under the transport policy
            # (== expert_bytes for fp32); slots hold this footprint too
            # when packed-resident, full width otherwise
            "expert_transport_bytes": transport_max,
            "device": self.device_bytes(caches),
        }
