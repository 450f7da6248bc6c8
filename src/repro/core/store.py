"""Host expert store + device expert slots (the "cacheless" memory model).

``ExpertStore`` holds every expert's FFN weights in host (numpy) memory —
the paper's CPU-DRAM tier.  ``WorkerSlots`` models the distributed worker
fleet: each worker owns a small number of device-resident expert slots
(the paper's <1 GB GPU footprint; exactly one by default, more when a
``repro.fleet.WorkerProfile`` grants a larger memory budget) plus
bookkeeping of what is resident, what is in flight, and which workers
are currently alive.  ``load`` physically copies host weights into a
slot (``jax.device_put``), so engine compute genuinely consumes slot
contents; eviction is removal or overwrite — there is no cache.  Slots
hold one of two representations: the default dequantize-on-arrival mode
reconstructs full-width weights as the shard lands, while
``packed_resident=True`` keeps the wire-format codes+scales resident in
their tile-aligned device layout and defers dequantization into the
fused grouped-GEMM kernel (``repro.kernels.moe_gemm.packed``) — same
bits, ~4-8x fewer slot bytes for int8/nf4 policies.  A
``fail``-ed worker loses its residents (the device is gone), which
forces reload-on-miss for anything it held; ``recover`` brings it back
empty.

All loads/evictions/hits/reloads are appended to an event log that the
discrete-event timing model replays with real hardware constants.

Stats semantics (pinned by tests/test_fleet.py):

  * ``evictions`` counts every resident expert displaced on a live
    worker — whether by ``load``'s capacity-overwrite path or by an
    explicit ``evict`` (the cacheless rule).  Both paths are the same
    event: a slot lost its occupant.
  * experts dropped because their worker *died* count under
    ``failure_drops``, never ``evictions`` — losing a device is not a
    scheduling decision.
  * ``hits`` count only loads that found their expert already resident;
    the engine evicts every worker it touched after each layer, so a
    mispredicted never-used resident cannot linger to fake a later hit.
  * ``bytes_moved`` (pinned by tests/test_transport.py) counts the
    *packed* transport payload of every physical load — what actually
    crossed the link under the store's ``PrecisionPolicy``.  Hits and
    failures move nothing.

Opportunistic residency (``repro.core.prefetch``) extends the model
without touching those semantics: ``release`` marks a worker's
residents *released* instead of evicting them — they keep occupying
free slots and a later ``load`` of the same expert re-hits in place (no
event, zero bytes) — while displacement pressure (a full worker taking
a new load) evicts released residents first, with the residency policy
choosing the victim.  Residency counters live in ``residency_stats``,
beside ``stats`` like ``bytes_moved``, so the scripted stats regression
stays byte-for-byte.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import MOE_FF, ModelConfig
from repro.models.transformer import layer_params
from repro.quant.transport import (EXPERT_WEIGHT_NAMES, PackedWeight,
                                   device_layout, resolve_policy,
                                   tileable)

from .spans import span


@jax.jit
def stack_shards(shards: Sequence[Dict[str, jax.Array]]
                 ) -> Dict[str, jax.Array]:
    """Stack experts' full-width weights along a new leading axis, in
    one program: ``{w_gate/w_up: (E, d, f), w_down: (E, f, d)}``.  Eager
    ``jnp.stack`` dispatches one op per expert and weight, each with its
    own output buffer, which costs the host more than the copy costs the
    device."""
    return {name: jnp.stack([s[name] for s in shards])
            for name in EXPERT_WEIGHT_NAMES}


@dataclass
class LoadEvent:
    token: int              # decoding iteration (serving: global step index)
    layer: int              # absolute layer index
    expert: int
    worker: int
    predicted: bool         # True: issued from SEP prediction; False: reload
    bytes: int              # packed transport payload that crossed the link
    requests: Tuple[int, ...] = ()   # serving: request ids sharing this load
    profile: Optional[object] = None  # fleet: the worker's WorkerProfile
    scheme: str = "fp32"    # transport precision this load shipped at


@dataclass(frozen=True)
class DeviceShard:
    """One expert's slot contents in packed-resident mode: the wire
    codes+scales rearranged into the tile-aligned device layout the
    fused kernel streams.  ``scheme == 'fp32'`` marks the fallback for
    shapes/dtypes with no tile-aligned layout — its parts are the
    full-width weights from dequantize-on-arrival, so mixed waves can
    always compute."""
    scheme: str
    parts: Dict[str, Tuple]       # weight name -> device-layout part tuple
    nbytes: int                   # resident device bytes of this shard


class ExpertStore:
    """Per-(layer, expert) host copies of the expert FFN weights, plus
    the pre-packed transport shards the worker links actually move.

    ``policy`` (a ``repro.quant.PrecisionPolicy``, scheme name, or
    ``None`` = fp32) fixes each expert's transport precision.  Shards
    are packed ONCE here — a load ships the cached packed bytes, never
    re-quantizes, and never copies the full FP32 tensors when a cheaper
    wire format exists (the fp32 shard aliases the host arrays, so the
    default path stays zero-copy too).
    """

    def __init__(self, cfg: ModelConfig, params, policy=None):
        self.cfg = cfg
        self.policy = resolve_policy(policy)
        self.moe_layers: List[int] = [
            i for i, (_, ff) in enumerate(cfg.layer_kinds()) if ff == MOE_FF]
        self._host: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
        self._packed: Dict[Tuple[int, int], Dict[str, PackedWeight]] = {}
        # each routed layer's (E, d, f) host arrays: views of host
        # ``numpy`` leaves (the store is then their only holder and
        # copies nothing), else one copy off the device
        self._layers: Dict[int, Dict[str, np.ndarray]] = {}
        pattern, _ = cfg.pattern()
        for li in self.moe_layers:
            ff = params["layers"][li % len(pattern)]["ff"]
            r = li // len(pattern)
            self._layers[li] = {n: np.asarray(ff[n][r])
                                for n in EXPERT_WEIGHT_NAMES}
            for e in range(cfg.num_experts):
                host = {n: self._layers[li][n][e]
                        for n in EXPERT_WEIGHT_NAMES}
                self._host[(li, e)] = host
                codec = self.policy.codec_for(li, e)
                self._packed[(li, e)] = {
                    n: codec.pack(host[n]) for n in EXPERT_WEIGHT_NAMES}
        sample = next(iter(self._host.values())) if self._host else {}
        self.expert_bytes = int(sum(a.nbytes for a in sample.values()))
        # tile-aligned device layouts (packed-resident mode), built lazily
        self._device_host: Dict[Tuple[int, int], Dict[str, Tuple]] = {}

    def stream_block(self, layer: int, lo: int, n: int
                     ) -> Dict[str, jax.Array]:
        """Experts ``lo .. lo + n`` of ``layer`` on the device, one
        transfer per weight from the contiguous host block (streamed
        prefill; fp32 transport)."""
        return jax.device_put({name: w[lo:lo + n]
                               for name, w in self._layers[layer].items()})

    def get_host(self, layer: int, expert: int) -> Dict[str, np.ndarray]:
        return self._host[(layer, expert)]

    def get_packed(self, layer: int, expert: int) -> Dict[str, PackedWeight]:
        """The cached wire-format shard (packed once at construction)."""
        return self._packed[(layer, expert)]

    def scheme_of(self, layer: int, expert: int) -> str:
        return self.policy.scheme_for(layer, expert)

    def packed_bytes(self, layer: int, expert: int) -> int:
        """Exact transport payload of one expert under the policy."""
        return sum(pw.nbytes
                   for pw in self._packed[(layer, expert)].values())

    def unpack_shard(self, layer: int, expert: int,
                     device: bool = True) -> Dict[str, jax.Array]:
        """Dequantize-on-arrival: reconstruct full-width weights from
        the packed shard.  ``device=True`` ships the packed parts to the
        device first (that transfer is the modeled link payload) and
        dequantizes there."""
        codec = self.policy.codec_for(layer, expert)
        if codec.scheme == "fp32" and not device:
            # bookkeeping-only fp32 loads alias the host copies outright
            # (the pre-codec zero-cost path)
            return self._host[(layer, expert)]
        packed = self._packed[(layer, expert)]
        # one batched transfer for the whole shard (all three weights'
        # packed parts), not one dispatch per part — the per-expert
        # payload is the modeled link unit anyway
        parts = (jax.device_put({n: pw.parts for n, pw in packed.items()})
                 if device else {n: None for n in packed})
        return {n: codec.unpack(pw, parts[n]) for n, pw in packed.items()}

    # --------------------------------------------- packed-resident mode
    def resident_tileable(self, layer: int, expert: int) -> bool:
        """Whether this expert can stay wire-format in its slot: every
        weight admits the tile-aligned device layout AND the deployment
        dtype is fp32 (in-kernel dequant produces fp32; a narrower
        deployment dtype would need the round-cast dequantize-on-arrival
        performs, so it falls back to keep bits identical)."""
        shard = self._packed[(layer, expert)]
        return all(tileable(pw.scheme, pw.shape) and pw.dtype == "float32"
                   for pw in shard.values())

    def resident_nbytes(self, layer: int, expert: int) -> int:
        """Device bytes this expert occupies in a packed-resident slot:
        the exact packed payload when tileable (the device layout is a
        pure reshape of the wire bytes), else the full-width fallback."""
        if self.resident_tileable(layer, expert):
            return self.packed_bytes(layer, expert)
        return self.expert_bytes

    def device_shard(self, layer: int, expert: int,
                     device: bool = True) -> DeviceShard:
        """Packed-resident sibling of :meth:`unpack_shard`: ship the
        wire bytes and keep them resident in tile-aligned layout (no
        dequantization — the fused kernel does it in-register).
        Untileable shapes/dtypes fall back to dequantize-on-arrival,
        tagged ``scheme='fp32'`` so downstream grouping treats them as
        full-width."""
        key = (layer, expert)
        scheme = self.scheme_of(layer, expert)
        if not self.resident_tileable(layer, expert):
            full = self.unpack_shard(layer, expert, device=device)
            return DeviceShard("fp32", {n: (full[n],) for n in full},
                               self.expert_bytes)
        if key not in self._device_host:
            self._device_host[key] = {
                n: device_layout(pw)
                for n, pw in self._packed[key].items()}
        host = self._device_host[key]
        parts = jax.device_put(host) if device else dict(host)
        return DeviceShard(scheme, parts, self.packed_bytes(layer, expert))

    def router_weights(self, params):
        """Routers live on the main node (non-expert parameters)."""
        return {li: layer_params(self.cfg, params, li)["ff"]["router"]
                for li in self.moe_layers}


class WorkerSlots:
    """``n_workers`` device expert-slot sets with load/evict/failure
    accounting.  ``profiles`` (``repro.fleet.WorkerProfile``s) give
    per-worker slot capacity and tag load events; omitted, every worker
    has the paper's single slot."""

    def __init__(self, store: ExpertStore, n_workers: int,
                 physical: bool = True,
                 profiles: Optional[Sequence] = None,
                 residency=None, packed_resident: bool = False):
        self.store = store
        self.n_workers = n_workers
        self.physical = physical  # False: bookkeep only (no device copies)
        self.residency = residency   # ResidencyPolicy or None (cacheless)
        # True: slots hold wire-format DeviceShards (codes+scales) and
        # the fused kernel dequantizes in-register; False (default):
        # dequantize-on-arrival, slots hold full-width weights
        self.packed_resident = packed_resident
        self.profiles = list(profiles) if profiles else None
        if self.profiles is not None and len(self.profiles) != n_workers:
            raise ValueError("one profile per worker required")
        self.capacity: List[int] = (
            [p.capacity for p in self.profiles] if self.profiles
            else [1] * n_workers)
        self.alive: List[bool] = [True] * n_workers
        # occupied slots per worker, oldest first (capacity overwrite
        # evicts FIFO); data keyed by (layer, expert)
        self._occupied: List[List[Tuple[int, int]]] = [
            [] for _ in range(n_workers)]
        self._slot_data: List[Dict[Tuple[int, int], dict]] = [
            {} for _ in range(n_workers)]
        self.events: List[LoadEvent] = []
        self.stats = {"loads": 0, "predicted_loads": 0, "reloads": 0,
                      "hits": 0, "evictions": 0, "failures": 0,
                      "recoveries": 0, "failure_drops": 0}
        # packed link bytes actually moved (pinned by test_transport):
        # kept beside ``stats`` so the scripted stats regression stays
        # byte-for-byte while transport accounting grows independently
        self.bytes_moved: int = 0
        # opportunistic-residency accounting, also beside ``stats``:
        # ``rehit_bytes_saved`` counts the packed payload a re-hit did
        # NOT move; ``evicted_bytes`` the full-width slot bytes every
        # eviction freed (capacity displacement or explicit evict)
        self._released: List[set] = [set() for _ in range(n_workers)]
        self.residency_stats = {"released": 0, "rehits": 0,
                                "rehit_bytes_saved": 0, "displaced": 0,
                                "evicted_bytes": 0}
        self._request_context: Tuple[int, ...] = ()

    @property
    def resident(self) -> List[Optional[object]]:
        """Per-worker residency view: ``None`` when empty, the single
        ``(layer, expert)`` when one expert is resident, else a tuple of
        them (capacity > 1)."""
        out: List[Optional[object]] = []
        for occ in self._occupied:
            out.append(None if not occ
                       else occ[0] if len(occ) == 1 else tuple(occ))
        return out

    def set_request_context(self, request_ids) -> None:
        """Tag subsequent load events with the composed batch's request
        ids.  One physical load then carries the full set of requests it
        serves — the amortization signal the serving benchmarks report."""
        self._request_context = tuple(int(r) for r in request_ids)

    @property
    def request_context(self) -> Tuple[int, ...]:
        """The request ids load events are tagged with right now."""
        return self._request_context

    # ------------------------------------------------------------- actions
    def load(self, token: int, layer: int, expert: int, worker: int,
             predicted: bool, payload: Optional[dict] = None) -> bool:
        """Ship (layer, expert)'s *packed* shard into a slot on
        ``worker``, so compute consumes the transported precision while
        only packed bytes cross the link.  Default mode dequantizes on
        arrival (the slot holds full-width weights); packed-resident
        mode keeps the wire bytes in the slot and the fused kernel
        dequantizes in-register — identical arithmetic either way.
        A full worker overwrites a resident: the residency policy's
        victim among released residents when one exists, else the
        oldest (FIFO — the historical cacheless behaviour, counted as
        an eviction either way).

        ``payload`` is an already-fetched ``unpack_shard`` result from
        the prefetch executor; commit then skips the inline fetch but
        accounts the identical packed bytes — prefetch moves WHEN the
        transfer happens, never what it costs.  Returns ``True`` when
        the load physically shipped, ``False`` on a hit/re-hit."""
        if not self.alive[worker]:
            raise RuntimeError(f"load onto dead worker {worker}")
        key = (layer, expert)
        if key in self._slot_data[worker]:
            if key in self._released[worker]:
                self._reactivate(worker, key)      # residency re-hit
            else:
                self.stats["hits"] += 1
            return False
        if len(self._occupied[worker]) >= self.capacity[worker]:
            victim = None
            if self.residency is not None:
                released = [k for k in self._occupied[worker]
                            if k in self._released[worker]]
                if released:
                    victim = self.residency.victim(released)
                    self.residency_stats["displaced"] += 1
            if victim is None:
                victim = self._occupied[worker][0]
            self._occupied[worker].remove(victim)
            self._released[worker].discard(victim)
            del self._slot_data[worker][victim]
            if self.residency is not None:
                self.residency.forget(victim)
            self.stats["evictions"] += 1
            self.residency_stats["evicted_bytes"] += \
                self._resident_nbytes(victim)
        nbytes = self.store.packed_bytes(layer, expert)
        data = payload
        if data is None:
            # the shipping path's host->device copy, inline (hits move
            # nothing; a prefetched payload was copied on its loader
            # thread, under a span of its own)
            with span("expert_load", layer=int(layer), expert=int(expert),
                      nbytes=nbytes, predicted=int(predicted), ahead=0):
                if self.packed_resident:
                    data = self.store.device_shard(layer, expert,
                                                   device=self.physical)
                else:
                    data = self.store.unpack_shard(layer, expert,
                                                   device=self.physical)
        self._slot_data[worker][key] = data
        self._occupied[worker].append(key)
        self.stats["loads"] += 1
        self.stats["predicted_loads" if predicted else "reloads"] += 1
        self.bytes_moved += nbytes
        if self.residency is not None:
            self.residency.note(key)
        self.events.append(LoadEvent(
            token, layer, expert, worker, predicted,
            nbytes, self._request_context,
            self.profiles[worker] if self.profiles else None,
            self.store.scheme_of(layer, expert)))
        return True

    # ---------------------------------------------------------- residency
    def _reactivate(self, worker: int, key: Tuple[int, int]) -> None:
        """A released resident is used again: un-release in place.  The
        re-hit saved exactly the packed payload a reload would have
        moved — no event, no bytes."""
        self._released[worker].discard(key)
        self.residency_stats["rehits"] += 1
        self.residency_stats["rehit_bytes_saved"] += \
            self.store.packed_bytes(*key)
        if self.residency is not None:
            self.residency.note(key)

    def reactivate(self, layer: int, expert: int) -> Optional[int]:
        """Claim a resident copy of (layer, expert) anywhere in the
        fleet: re-hit accounting when it was released, plain claim when
        it is already active.  Returns the hosting worker, or ``None``
        when nothing is resident (the caller loads normally)."""
        key = (layer, expert)
        for w in range(self.n_workers):
            if self.alive[w] and key in self._slot_data[w]:
                if key in self._released[w]:
                    self._reactivate(w, key)
                return w
        return None

    def claim_resident(self, layer: int, expert: int, worker: int) -> bool:
        """Wave-time claim of a known-resident expert on ``worker``:
        un-release it when released (a reload avoided).  Returns whether
        a re-hit happened."""
        key = (layer, expert)
        if key in self._released[worker]:
            self._reactivate(worker, key)
            return True
        return False

    def is_released(self, worker: int, layer: int, expert: int) -> bool:
        return (layer, expert) in self._released[worker]

    def release(self, worker: int) -> None:
        """Opportunistic residency: instead of the cacheless eviction,
        mark the worker's residents released — they stay in their free
        slots until displaced and a matching later load re-hits.
        Without a policy this degrades to ``evict`` (cacheless)."""
        if self.residency is None:
            self.evict(worker)
            return
        newly = [k for k in self._occupied[worker]
                 if k not in self._released[worker]]
        self.residency_stats["released"] += len(newly)
        self._released[worker].update(newly)

    def observe_gates(self, layer: int, true, gates) -> None:
        """Feed the router's realized routing into the residency policy
        (gate-statistics popularity).  Deterministic accumulation order:
        keys ascending."""
        if self.residency is None:
            return
        mass: Dict[Tuple[int, int], float] = {}
        t = np.asarray(true)
        g = np.asarray(gates)
        for b in range(t.shape[0]):
            for j in range(t.shape[1]):
                key = (layer, int(t[b, j]))
                mass[key] = mass.get(key, 0.0) + abs(float(g[b, j]))
        for key in sorted(mass):
            self.residency.credit(key, mass[key])

    def _resident_nbytes(self, key: Tuple[int, int]) -> int:
        """Device bytes one resident expert occupies — full width in the
        default mode, the packed payload in packed-resident mode (the
        pricing every eviction/displacement charge uses)."""
        if self.packed_resident:
            return self.store.resident_nbytes(*key)
        return self.store.expert_bytes

    def resident_slot_bytes(self, worker: int) -> int:
        """Device bytes currently held by ``worker``'s occupied slots
        (active + released residents) — full-width in the default mode,
        packed in packed-resident mode."""
        return sum(self._resident_nbytes(k)
                   for k in self._occupied[worker])

    def slot(self, worker: int, layer: int, expert: int) -> dict:
        assert self.alive[worker], "dead worker used"
        data = self._slot_data[worker].get((layer, expert))
        assert data is not None, "expert must be resident"
        return data

    def gather_stack(self, layer: int,
                     wave: Dict[int, int]) -> Tuple[List[int], Dict]:
        """Materialize one wave's resident expert weights as stacked
        arrays for the grouped FFN kernel: ``wave`` maps expert ->
        serving worker; returns ``(experts, {w_gate/w_up: (E_wave, d,
        f), w_down: (E_wave, f, d)})`` with the expert order fixed
        (ascending id) so the stacked axis is deterministic.  Gathers
        through :meth:`slot`, which asserts each expert is *physically
        resident* on its assigned worker — the grouped hot path still
        consumes genuine slot contents, never the host store."""
        experts = sorted(wave)
        return experts, stack_shards(
            [self.slot(wave[e], layer, e) for e in experts])

    def gather_stack_packed(self, layer: int, wave: Dict[int, int]):
        """Packed-resident sibling of :meth:`gather_stack`: stack each
        wave expert's wire-format parts (codes + scales) instead of
        full-width fp32.  Because a ``TieredPolicy`` can mix schemes in
        one wave (and untileable experts fall back to full width), the
        wave splits into per-scheme groups — one fused grouped call
        each.  Masked pairs contribute exact zeros, so per-scheme
        sub-waves cannot change any request's bits (the repo's standing
        wave-partitioning invariant).

        Returns ``(experts, groups)``: ``experts`` is the full ascending
        wave order, ``groups`` a list of ``(scheme, expert_ids, parts)``
        with ``parts`` mapping each weight name to its stacked
        device-layout part tuple — exactly what
        ``grouped_topk_contrib_packed`` consumes."""
        experts = sorted(wave)
        shards = [self.slot(wave[e], layer, e) for e in experts]
        groups = []
        for scheme in dict.fromkeys(s.scheme for s in shards):
            sel = [(e, s) for e, s in zip(experts, shards)
                   if s.scheme == scheme]
            eids = [e for e, _ in sel]
            parts = {
                name: tuple(
                    jnp.stack([s.parts[name][j] for _, s in sel])
                    for j in range(len(sel[0][1].parts[name])))
                for name in EXPERT_WEIGHT_NAMES}
            groups.append((scheme, eids, parts))
        return experts, groups

    def worker_with(self, layer: int, expert: int) -> Optional[int]:
        key = (layer, expert)
        for w in range(self.n_workers):
            if self.alive[w] and key in self._slot_data[w]:
                return w
        return None

    def evict(self, worker: int) -> None:
        """Prompt eviction after the expert computation (cacheless rule):
        drop everything resident on ``worker``."""
        n = len(self._occupied[worker])
        self.stats["evictions"] += n
        self.residency_stats["evicted_bytes"] += sum(
            self._resident_nbytes(k) for k in self._occupied[worker])
        if self.residency is not None:
            for k in self._occupied[worker]:
                self.residency.forget(k)
        self._occupied[worker] = []
        self._slot_data[worker] = {}
        self._released[worker].clear()

    # ------------------------------------------------------------ failures
    def fail(self, worker: int) -> None:
        """The worker's device is gone: mark dead and lose its residents
        (``failure_drops``, not evictions) — anything it held must be
        reloaded elsewhere on miss."""
        if not self.alive[worker]:
            return
        self.alive[worker] = False
        self.stats["failures"] += 1
        self.stats["failure_drops"] += len(self._occupied[worker])
        if self.residency is not None:
            for k in self._occupied[worker]:
                self.residency.forget(k)
        self._occupied[worker] = []
        self._slot_data[worker] = {}
        self._released[worker].clear()

    def recover(self, worker: int) -> None:
        """The worker rejoins with empty slots."""
        if self.alive[worker]:
            return
        self.alive[worker] = True
        self.stats["recoveries"] += 1

    # -------------------------------------------------------------- memory
    def resident_device_bytes(self) -> int:
        """Device bytes the occupied slots hold now."""
        total = 0
        for slots in self._slot_data:
            for data in slots.values():
                if isinstance(data, DeviceShard):
                    total += data.nbytes
                else:
                    total += sum(int(a.size) * a.dtype.itemsize
                                 for a in jax.tree.leaves(data)
                                 if isinstance(a, jax.Array))
        return total

    def transient_packed_bytes(self) -> int:
        """Largest in-flight packed shard during dequantize-on-arrival.

        While a non-fp32 shard unpacks, the packed wire buffer AND the
        full-width slot tensors are both live on the device; the fp32
        path aliases the arriving buffer outright, so it double-buffers
        nothing.  Peak over the policy therefore counts only experts
        shipped below full width (pinned against
        ``ExpertStore.packed_bytes`` by tests/test_transport.py).

        In packed-resident mode tileable experts never dequantize on
        arrival — the arriving wire buffer IS the slot content (a pure
        reshape), so nothing double-buffers; only untileable fallback
        experts still pay the transient.
        """
        store = self.store
        return max(
            (store.packed_bytes(li, e)
             for li in store.moe_layers
             for e in range(store.cfg.num_experts)
             if store.scheme_of(li, e) != "fp32"
             and not (self.packed_resident
                      and store.resident_tileable(li, e))),
            default=0)

    def slot_unit_bytes(self) -> int:
        """Device bytes one slot must provision: the full-width expert
        in the default mode, the largest resident shard (packed when
        tileable, full-width fallback otherwise) in packed-resident
        mode."""
        if not self.packed_resident:
            return self.store.expert_bytes
        store = self.store
        return max(
            (store.resident_nbytes(li, e)
             for li in store.moe_layers
             for e in range(store.cfg.num_experts)),
            default=store.expert_bytes)

    def device_bytes_per_worker(self) -> int:
        """Peak device bytes per worker — the paper's '<1 GB per
        worker' quantity: the resident slots (scaled by the largest
        slot capacity in the fleet) plus the transient packed buffer
        live during dequantize-on-arrival.  fp32 transport keeps the
        historical slots-only value; packed-resident slots shrink the
        slot term to the wire footprint (pinned strictly below the
        fp32-slot baseline by tests/test_packed_kernel.py)."""
        return (self.slot_unit_bytes() * max(self.capacity)
                + self.transient_packed_bytes())
