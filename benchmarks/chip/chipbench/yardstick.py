"""The comparison that decides ``correct``.

For each judged request, the float32 reference (the ``forward`` of the
configuration's architecture plug-in, ``arch/<model_type>.py``) runs
once over its prompt and served tokens, teacher-forced on those tokens
and on the routing the program served each decode step with.  Three
numbers over every judged decode step:

* ``token_gap`` (the widest over steps): how far the served token's
  reference logit lies below the reference's best, in RMS units of the
  reference logits;
* ``logit_err`` (the mean over steps): relative RMS error of the
  program's logits against the reference's (``||prog - ref|| /
  ||ref||``);
* ``route_gap`` (the mean over layers and steps): how far the weakest
  expert the program routed to lies below the reference's k-th best
  router logit, in RMS units of the reference router logits (0 where
  the sets agree; a bfloat16 near-tie swap reads a few hundredths, a
  wrong expert about 1).

The prompt's own positions run with the reference's routing (the
program does not report its prefill routing); the prefill is judged
through every decode step that attends its cache.  The first token,
which comes from the prefill at a position whose routing is not forced,
is taken as given.

The control (``control_readings``) puts the reference in the program's
place one precision step below the configuration's bfloat16: float8
(e4m3) weights, computed in bfloat16.  At each position of the same
prompts and tokens it takes the token and the routing that forward puts
first, and the float32 reference, forced on that routing, reads the
same numbers.  (int8 per channel is the other step down; it reads only
about 3x the program's logit error, float8 about 13x, on the CPU test
model.)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

NUMBERS = ("token_gap", "logit_err", "route_gap")


@dataclass
class RefOut:
    """What a plug-in's ``forward`` returns."""
    logits: np.ndarray               # (P, V) at the judged positions
    routing: Dict[int, np.ndarray]   # layer -> (P, k) experts used there
    router: Dict[int, np.ndarray]    # layer -> (P, E) router logits there


@dataclass
class Served:
    """One request as the timed path served it."""
    prompt: np.ndarray                 # (T0,) int32
    tokens: np.ndarray                 # (n,) int32, tokens[0] from prefill
    routing: List[Dict[int, np.ndarray]]   # per decode step: layer -> (k,)
    logits: Optional[List[np.ndarray]]     # per decode step: (V,)

    @property
    def steps(self) -> int:
        return len(self.tokens) - 1


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))


def _kth(x: np.ndarray, k: int) -> float:
    return float(np.partition(x, -k)[-k])


def step_readings(ref: RefOut, tokens: np.ndarray,
                  logits: Optional[Sequence[np.ndarray]],
                  routing: Dict[int, np.ndarray], k: int
                  ) -> Dict[str, List[float]]:
    """Per-step readings of one request.  ``tokens`` (P,) are the tokens
    judged at the P positions, ``logits`` the side's own logits there
    (None: not reported), ``routing`` layer -> (P, k)."""
    out: Dict[str, List[float]] = {n: [] for n in NUMBERS}
    for j, t in enumerate(tokens):
        r = ref.logits[j]
        out["token_gap"].append(float(r.max() - r[int(t)]) / _rms(r))
        if logits is not None:
            e = np.asarray(logits[j], np.float64)
            out["logit_err"].append(float(np.linalg.norm(e - r)
                                          / np.linalg.norm(r)))
        for li, chosen in routing.items():
            g = ref.router[li][j]
            out["route_gap"].append(
                (_kth(g, k) - float(g[np.asarray(chosen[j])].min()))
                / _rms(g))
    return out


def _positions(s: Served):
    seq = np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
    t0 = len(s.prompt)
    return seq, np.arange(t0, t0 + s.steps, dtype=np.int32)


def program_readings(plugin, arch, params, s: Served
                     ) -> Dict[str, List[float]]:
    """The compared numbers of one served request: ``plugin`` is the
    architecture plug-in, ``arch`` its ``Arch`` of the configuration."""
    if s.steps < 1:
        return {n: [] for n in NUMBERS}
    seq, pos = _positions(s)
    forced = {li: np.stack([step[li] for step in s.routing])
              for li in sorted(s.routing[0])}
    ref = plugin.forward(arch, params, seq, pos, forced=forced)
    return step_readings(ref, s.tokens[1:], s.logits, forced, arch.top_k)


def control_readings(plugin, arch, params, s: Served, mode: str = "fp8"
                     ) -> Dict[str, List[float]]:
    if s.steps < 1:
        return {n: [] for n in NUMBERS}
    seq, pos = _positions(s)
    ctl = plugin.forward(arch, params, seq, pos, mode=mode)
    ref = plugin.forward(arch, params, seq, pos, forced=ctl.routing)
    return step_readings(ref, ctl.logits.argmax(-1), list(ctl.logits),
                         ctl.routing, arch.top_k)


def summarize(parts: Sequence[Dict[str, List[float]]]) -> Dict[str, float]:
    """The compared numbers over every judged step of every request:
    the widest ``token_gap``, and the mean ``logit_err`` and
    ``route_gap`` (a step's worst-case logit error and the widest route
    gap swing with the few steps whose prompt routed the other way in
    the program's bfloat16 prefill, which the check cannot force; see
    PERF.md).  The widest of those two are reported beside them."""
    steps = {n: [v for p in parts for v in p[n]] for n in NUMBERS}
    out = {"token_gap": max(steps["token_gap"], default=0.0)}
    for n in ("logit_err", "route_gap"):
        out[n] = float(np.mean(steps[n])) if steps[n] else 0.0
        out[n + "_max"] = max(steps[n], default=0.0)
    return out


def judge(readings: Dict[str, float], limits: Dict[str, Optional[float]]):
    """``(correct, [(name, value, limit)])``: every compared number at or
    under its limit.  A number whose limit is ``null`` in the cell's
    limits file is not compared (its two readings do not separate)."""
    rows = [(n, readings[n], float(limits[n])) for n in NUMBERS
            if limits.get(n) is not None]
    return all(v <= lim for _, v, lim in rows), rows
