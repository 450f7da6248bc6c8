"""Mixed-precision on-demand expert transport (HOBBIT-style).

OD-MoE's decode speed is gated by Eq. (1): ``t_load = expert_bytes /
link_bandwidth``.  The paper ships every on-demand expert at full
precision; HOBBIT (arXiv:2411.01433) shows that shipping less-critical
experts at lower precision cuts expert-loading latency with negligible
quality loss, because I/O bytes — not compute — dominate edge MoE
serving.  This module is the wire format + policy layer for that idea:

  * ``TransportCodec`` — fp32 / fp16 / int8 / nf4 pack->unpack of one
    expert weight matrix, reusing the ``repro.quant`` quantizers.  The
    packed representation is what moves over the link.  In the default
    mode workers dequantize on arrival (device slots hold full-width
    weights); in packed-resident mode (``WorkerSlots(...,
    packed_resident=True)``) the slot keeps the wire format — rearranged
    by :func:`device_layout` into tile-aligned codes + scales — and the
    fused Pallas kernel dequantizes in-register immediately before the
    MXU dots, so slot bytes AND kernel HBM traffic shrink to the wire
    size.  ``nbytes`` of the packed parts is the exact transport
    payload — int8 carries per-channel scales, nf4 carries bit-packed
    4-bit codes plus per-block absmax scales.
  * ``PrecisionPolicy`` — which scheme each (layer, expert) ships at.
    ``UniformPolicy`` is one scheme fleet-wide; ``TieredPolicy`` is the
    HOBBIT rule: experts the router historically picks with low gate
    weight (low confidence -> low criticality) ship at the cheaper
    scheme, the rest at the higher one.
  * ``transport_params`` — the *reference* side of the invariant: the
    same quantize->dequantize round trip applied to a parameter tree,
    so ``greedy_generate(..., transport=policy)`` consumes exactly the
    weight values a worker reconstructs on arrival.  Decode therefore
    stays token-bit-identical to the reference *under the same
    transport policy* — precision is part of the model contract, never
    a scheduling side effect.  For that to hold, a scheme must be a
    pure function of (layer, expert): per-worker or per-load precision
    would make arithmetic depend on scheduling and is deliberately
    unsupported.
  * ``transport_expert_bytes`` — closed-form packed bytes of one expert
    (w_gate/w_up/w_down) for a full-size config, used by the timing
    model to price per-link ``t_load`` by *packed* bytes.  Pinned by
    tests to equal ``TransportCodec.pack``'s actual payload exactly.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.models.config import MOE_FF, ModelConfig

from .quantize import (EXPERT_WEIGHT_NAMES, NF4_BLOCK, dequantize_int8,
                       dequantize_nf4, pack_nf4_codes, quantize_int8,
                       quantize_nf4, unpack_nf4_codes)

SCHEMES = ("fp32", "fp16", "int8", "nf4")


@dataclass(frozen=True)
class PackedWeight:
    """One expert weight matrix in wire format: the arrays that would
    cross the link, plus what is needed to reconstruct the original."""
    scheme: str
    shape: Tuple[int, ...]
    dtype: str                       # dtype the unpacked weight restores to
    parts: Tuple[np.ndarray, ...]

    @property
    def nbytes(self) -> int:
        """Exact transport payload of this weight."""
        return int(sum(p.nbytes for p in self.parts))


class TransportCodec:
    """Pack/unpack one weight matrix at a transport precision.

    ``fp32`` is the identity wire format (ship the deployment dtype
    untouched) — packing it never copies and unpacking returns the same
    values bit-for-bit, which is what keeps the default transport path
    byte- and bit-identical to the pre-codec repo.
    """

    def __init__(self, scheme: str):
        if scheme not in SCHEMES:
            raise ValueError(f"unknown transport scheme {scheme!r}; "
                             f"expected one of {SCHEMES}")
        self.scheme = scheme

    # ------------------------------------------------------------- pack
    def pack(self, w) -> PackedWeight:
        shape = tuple(int(s) for s in w.shape)
        dtype = str(w.dtype)
        if self.scheme == "fp32":
            parts = (np.asarray(w),)
        elif self.scheme == "fp16":
            parts = (np.asarray(jnp.asarray(w).astype(jnp.float16)),)
        elif self.scheme == "int8":
            q, scale = quantize_int8(jnp.asarray(w))
            parts = (np.asarray(q), np.asarray(scale))
        else:                                                   # nf4
            codes, scales = quantize_nf4(jnp.asarray(w))
            parts = (np.asarray(pack_nf4_codes(codes)), np.asarray(scales))
        return PackedWeight(self.scheme, shape, dtype, parts)

    # ----------------------------------------------------------- unpack
    def unpack(self, pw: PackedWeight, parts: Optional[tuple] = None):
        """Reconstruct the weight from wire format (dequantize-on-
        arrival).  ``parts`` may override ``pw.parts`` with device
        copies — the arithmetic is identical either way."""
        parts = pw.parts if parts is None else parts
        if pw.scheme == "fp32":
            return jnp.asarray(parts[0])
        if pw.scheme == "fp16":
            w = parts[0].astype(jnp.float32)
        elif pw.scheme == "int8":
            w = dequantize_int8(jnp.asarray(parts[0]), jnp.asarray(parts[1]))
        else:                                                   # nf4
            n = 1
            for s in pw.shape:
                n *= s
            n_blocks = -(-n // NF4_BLOCK)
            codes = unpack_nf4_codes(jnp.asarray(parts[0]), n_blocks)
            w = dequantize_nf4(codes, jnp.asarray(parts[1]), pw.shape)
        return w.astype(jnp.dtype(pw.dtype))

    def round_trip(self, w):
        """quantize->dequantize at this precision — the exact weight
        values a worker holds after a transported load."""
        return self.unpack(self.pack(w))

    # ------------------------------------------------------- accounting
    def packed_nbytes(self, shape: Tuple[int, ...],
                      elem_bytes: int = 4) -> int:
        """Closed-form transport payload for a weight of ``shape`` whose
        deployment dtype is ``elem_bytes`` wide.  Pinned by tests to
        equal ``pack(...).nbytes`` exactly."""
        size = 1
        for s in shape:
            size *= int(s)
        if self.scheme == "fp32":
            return size * elem_bytes
        if self.scheme == "fp16":
            return size * 2
        if self.scheme == "int8":
            # int8 codes + one f32 scale per output channel (last axis)
            last = int(shape[-1]) if shape else 1
            return size + 4 * last
        # nf4: two 4-bit codes per byte over the 64-padded flat length,
        # plus one f32 absmax per block
        padded = -(-size // NF4_BLOCK) * NF4_BLOCK
        return padded // 2 + 4 * (padded // NF4_BLOCK)


# ------------------------------------------- tile-aligned device layout
def tileable(scheme: str, shape: Tuple[int, ...]) -> bool:
    """Whether a weight of ``shape`` admits the tile-aligned device
    layout at ``scheme`` — the precondition for packed-resident slots
    and the fused in-kernel-dequant grouped GEMM.

    fp32/fp16 tiles trivially; int8's per-output-channel scale row
    ``(1, last)`` slices along any last-axis blocking; nf4's absmax
    blocks run over the FLAT weight in 64-element strides, so they
    coincide with contiguous 64-column runs of one row (sliceable along
    the kernel's Fb blocks) exactly when the last axis is a multiple of
    ``NF4_BLOCK``.  Misaligned shapes keep the dequantize-on-arrival
    path — a fallback, never an error."""
    if scheme in ("fp32", "fp16"):
        return True
    if len(shape) != 2:
        return False
    if scheme == "int8":
        return True
    if scheme == "nf4":
        return shape[-1] % NF4_BLOCK == 0
    return False


def device_layout(pw: PackedWeight) -> Tuple[np.ndarray, ...]:
    """Rearrange a wire-format shard into the tile-aligned device
    layout the packed Pallas kernel streams: a pure, lossless reshape
    of the SAME codes and scales, so dequantizing either layout yields
    bit-identical weights.

      * fp32/fp16/int8 — already tile-aligned (int8 scales are one
        ``(1, last)`` row that slices along the same Fb blocks as the
        weight tiles); returned as-is.
      * nf4 — flat packed codes ``(n/2,)`` -> ``(d, f/2)`` (two
        f-adjacent 4-bit codes per byte, high nibble first) and flat
        block absmax ``(n/64, 1)`` -> ``(d, f/64)``; requires
        ``tileable`` (last axis % 64 == 0), which makes every absmax
        block one contiguous 64-column run of one row.
    """
    if not tileable(pw.scheme, pw.shape):
        raise ValueError(f"shape {pw.shape} has no tile-aligned device "
                         f"layout at {pw.scheme!r}")
    if pw.scheme != "nf4":
        return pw.parts
    d, f = pw.shape
    return (pw.parts[0].reshape(d, f // 2),
            pw.parts[1].reshape(d, f // NF4_BLOCK))


_CODECS: Dict[str, TransportCodec] = {}


def get_codec(scheme: str) -> TransportCodec:
    if scheme not in _CODECS:
        _CODECS[scheme] = TransportCodec(scheme)
    return _CODECS[scheme]


# ------------------------------------------------------------------ policy
class PrecisionPolicy:
    """Maps (layer, expert) -> transport scheme.  Must be a pure
    function of its arguments (see module docstring): the engine, the
    serving loop, the timing model and the reference decoder all consult
    the same policy and must see the same answer."""

    def scheme_for(self, layer: int, expert: int) -> str:
        raise NotImplementedError

    @property
    def default_scheme(self) -> str:
        """Scheme assumed for loads whose expert identity is unknown
        (timing-model padding loads)."""
        raise NotImplementedError

    @property
    def trivial(self) -> bool:
        """True when every expert ships fp32 — the pre-codec fast path."""
        return False

    def codec_for(self, layer: int, expert: int) -> TransportCodec:
        return get_codec(self.scheme_for(layer, expert))

    def describe(self) -> str:
        return type(self).__name__


@dataclass(frozen=True)
class UniformPolicy(PrecisionPolicy):
    """Every expert ships at one scheme (the paper's implicit fp32)."""
    scheme: str = "fp32"

    def __post_init__(self):
        if self.scheme not in SCHEMES:
            raise ValueError(f"unknown transport scheme {self.scheme!r}")

    def scheme_for(self, layer: int, expert: int) -> str:
        return self.scheme

    @property
    def default_scheme(self) -> str:
        return self.scheme

    @property
    def trivial(self) -> bool:
        return self.scheme == "fp32"

    def describe(self) -> str:
        return f"uniform/{self.scheme}"


class TieredPolicy(PrecisionPolicy):
    """HOBBIT-style confidence tiering: experts the router historically
    selects with low gate weight are less critical — mis-rounding them
    moves little probability mass — so they ship at the cheaper scheme.

    The tier assignment is decided once (from a calibration trace or an
    explicit set) and is static thereafter, which is what keeps decode
    bit-identical to the reference under the same policy even when
    batches compose differently or workers die.
    """

    def __init__(self, low_experts: Iterable[Tuple[int, int]],
                 high: str = "fp16", low: str = "int8"):
        if high not in SCHEMES or low not in SCHEMES:
            raise ValueError("unknown transport scheme in tiered policy")
        self.high, self.low = high, low
        self.low_experts = frozenset(
            (int(l), int(e)) for l, e in low_experts)

    def scheme_for(self, layer: int, expert: int) -> str:
        return (self.low if (layer, expert) in self.low_experts
                else self.high)

    @property
    def default_scheme(self) -> str:
        return self.high

    @property
    def trivial(self) -> bool:
        return self.high == "fp32" and (
            not self.low_experts or self.low == "fp32")

    def describe(self) -> str:
        return (f"tiered/{self.high}+{self.low}"
                f"[{len(self.low_experts)} low]")

    @classmethod
    def from_trace(cls, trace, low_fraction: float = 0.5,
                   high: str = "fp16", low: str = "int8",
                   num_experts: Optional[int] = None) -> "TieredPolicy":
        """Build the tier map from a calibration trace: per (layer,
        expert), confidence = mean gate weight when selected (selection
        count when the trace predates gate recording); per layer, the
        bottom ``low_fraction`` of *seen* experts ship at ``low``.
        Unseen experts are the least critical of all and always ship
        low — pass the config's ``num_experts`` so that covers experts
        the calibration run never routed to (inferred from the trace's
        largest routed index otherwise)."""
        if not 0.0 <= low_fraction <= 1.0:
            raise ValueError("low_fraction must be in [0, 1]")
        gate_sum: Dict[Tuple[int, int], float] = {}
        count: Dict[Tuple[int, int], int] = {}
        layers: Dict[int, set] = {}
        num_experts = int(num_experts or 0)
        for rec in trace.records:
            for lr in rec.layers:
                true = np.asarray(lr.true)
                gates = getattr(lr, "gates", None)
                gates = None if gates is None else np.asarray(gates)
                num_experts = max(num_experts, int(true.max()) + 1)
                layers.setdefault(lr.layer, set())
                for bi in range(true.shape[0]):
                    for j in range(true.shape[1]):
                        key = (lr.layer, int(true[bi, j]))
                        count[key] = count.get(key, 0) + 1
                        layers[lr.layer].add(int(true[bi, j]))
                        if gates is not None:
                            gate_sum[key] = (gate_sum.get(key, 0.0)
                                             + float(gates[bi, j]))
        low_set = set()
        for layer, seen in layers.items():
            def conf(e):
                key = (layer, e)
                if key in gate_sum:
                    return gate_sum[key] / count[key]
                return float(count.get(key, 0))
            ranked = sorted(seen, key=lambda e: (conf(e), e))
            n_low = int(math.floor(low_fraction * len(ranked)))
            low_set.update((layer, e) for e in ranked[:n_low])
            low_set.update((layer, e) for e in range(num_experts)
                           if e not in seen)
        return cls(low_set, high=high, low=low)


def resolve_policy(spec) -> PrecisionPolicy:
    """None -> fp32 identity; a scheme name -> ``UniformPolicy``; a
    policy -> itself."""
    if spec is None:
        return UniformPolicy("fp32")
    if isinstance(spec, PrecisionPolicy):
        return spec
    if isinstance(spec, str):
        return UniformPolicy(spec)
    raise TypeError(f"cannot resolve transport policy from {spec!r}")


# --------------------------------------------------------- reference side
def transport_params(cfg: ModelConfig, params, policy,
                     packed=None) -> dict:
    """The reference decoder's view of a transport policy: every MoE
    expert weight replaced by its codec round trip, via the *same*
    pack/unpack functions the store and the workers use — so reference
    and engine consume bit-identical expert values.  Non-expert
    parameters (routers, attention, norms, embeddings) never transit
    the expert link and stay untouched.

    ``packed`` (optional, ``(layer, expert) -> {name: PackedWeight}``,
    e.g. ``ExpertStore.get_packed``) reuses already-packed shards so the
    quantize pass runs once per weight, not once per consumer — the
    unpack of a cached pack is bit-identical to a fresh round trip."""
    policy = resolve_policy(policy)
    if policy.trivial:
        return params
    pattern, reps = cfg.pattern()
    new_layers = []
    for pos, kinds in enumerate(pattern):
        sub = params["layers"][pos]
        if kinds[1] != MOE_FF:
            new_layers.append(sub)
            continue
        ff = dict(sub["ff"])
        for name in EXPERT_WEIGHT_NAMES:
            w = ff[name]                        # (reps, ep, d, f)
            per_rep = []
            for r in range(reps):
                li = r * len(pattern) + pos
                per_e = []
                for e in range(w.shape[1]):
                    if e >= cfg.num_experts:    # inert pad rows
                        per_e.append(w[r, e])
                    elif packed is not None:
                        pw = packed(li, e)[name]
                        per_e.append(get_codec(pw.scheme).unpack(pw))
                    else:
                        codec = policy.codec_for(li, e)
                        per_e.append(codec.round_trip(w[r, e]))
                per_rep.append(jnp.stack(per_e))
            ff[name] = jnp.stack(per_rep).astype(w.dtype)
        new_sub = dict(sub)
        new_sub["ff"] = ff
        new_layers.append(new_sub)
    out = dict(params)
    out["layers"] = tuple(new_layers)
    return out


# ------------------------------------------------------------- accounting
def expert_weight_shapes(cfg: ModelConfig) -> Tuple[Tuple[int, int], ...]:
    """The three FFN matrices one expert ships: w_gate, w_up, w_down."""
    d, f = cfg.d_model, cfg.d_expert_resolved
    return ((d, f), (d, f), (f, d))


def transport_expert_bytes(cfg: ModelConfig, scheme: str,
                           weight_bytes: int = 4) -> int:
    """Exact packed transport bytes of ONE expert at ``scheme`` for a
    (possibly full-size) config.  ``weight_bytes`` is the deployment
    element width (``HardwareProfile.weight_bytes``); fp32 transport
    ships it untouched, so the fp32 value equals the timing model's
    classic ``layer_bytes(cfg, wb)["expert"]``."""
    codec = get_codec(scheme)
    return sum(codec.packed_nbytes(shape, elem_bytes=weight_bytes)
               for shape in expert_weight_shapes(cfg))
