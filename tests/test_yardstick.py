"""The float32 yardstick (repro.core.yardstick): passes an engine decode
of a bf16 model, and flags a wrong token, wrong logits and wrong routing."""
import copy
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ODMoEEngine
from repro.core.yardstick import check_decode, float32_reference, merge
from repro.serve import Request, ServingLoop

N_TOK = 6


@pytest.fixture(scope="module")
def decoded():
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                              dtype="bfloat16")
    from repro.models import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(3, 11, dtype=np.int32)
    eng = ODMoEEngine(cfg, params, predictor="none", keep_logits=True)
    toks, trace = eng.generate({"tokens": prompt[None]}, N_TOK)
    return cfg, params, prompt, np.asarray(toks)[0], trace


def test_engine_decode_passes(decoded):
    cfg, params, prompt, toks, trace = decoded
    rep = check_decode(cfg, params, prompt, toks, trace.records,
                       trace.logits)
    assert rep.ok, rep.describe()
    assert rep.steps == N_TOK - 1 and rep.agreeing_steps == rep.steps


@pytest.mark.parametrize("speculate", [1, 2])
def test_served_logits_follow_each_request(decoded, speculate):
    """A composed (and, with speculation, multi-row) serving step keeps
    one logits row per emitted token in each request's own trace."""
    cfg, params = decoded[:2]
    reqs = [Request(rid=i, prompt=np.arange(3 + i, 9 + 2 * i,
                                            dtype=np.int32),
                    max_new_tokens=N_TOK - i) for i in range(3)]
    eng = ODMoEEngine(cfg, params, speculate=speculate, keep_logits=True)
    res = ServingLoop(eng, max_batch=3).run(reqs)
    for r in reqs:
        out, trace = res.outputs[r.rid], res.states[r.rid].trace
        assert len(trace.logits) == len(out) - 1
        assert [int(np.argmax(lg)) for lg in trace.logits] == list(out[1:])
        rep = check_decode(cfg, params, r.prompt, out, trace.records,
                           trace.logits)
        assert rep.ok and rep.max_rel is not None, rep.describe()
    eng.close()


def test_logits_are_kept_only_on_request(decoded):
    cfg, params, prompt = decoded[:3]
    eng = ODMoEEngine(cfg, params, predictor="none")
    _, trace = eng.generate({"tokens": prompt[None]}, 3)
    assert trace.logits == [] and eng.last_logits is not None


def test_flags_a_wrong_last_token(decoded):
    cfg, params, prompt, toks, trace = decoded
    seq = np.concatenate([prompt, toks[:-1]])[None]
    ref, _ = float32_reference(cfg, params, seq)
    bad = toks.copy()
    bad[-1] = int(np.argmin(ref[0, -1]))
    assert not check_decode(cfg, params, prompt, bad, trace.records).ok


def test_flags_wrong_logits(decoded):
    cfg, params, prompt, toks, trace = decoded
    noisy = [lg[:, ::-1] for lg in trace.logits]    # vocabulary permuted
    assert not check_decode(cfg, params, prompt, toks, trace.records,
                            noisy).ok


def test_flags_wrong_routing(decoded):
    cfg, params, prompt, toks, trace = decoded
    records = copy.deepcopy(trace.records)
    for rec in records:
        for lr in rec.layers:
            lr.true = (lr.true + 1) % cfg.num_experts
    rep = check_decode(cfg, params, prompt, toks, records)
    assert rep.route_agreement < 0.5 and not rep.ok


def test_routing_rows_must_match_tokens(decoded):
    cfg, params, prompt, toks, trace = decoded
    with pytest.raises(ValueError):
        check_decode(cfg, params, prompt, toks, trace.records[:-1])


def test_merge_pools_steps_and_keeps_worst_bounds(decoded):
    cfg, params, prompt, toks, trace = decoded
    good = check_decode(cfg, params, prompt, toks, trace.records)
    records = copy.deepcopy(trace.records)
    for rec in records:
        for lr in rec.layers:
            lr.true = (lr.true + 1) % cfg.num_experts
    bad = check_decode(cfg, params, prompt, toks, records)
    both = merge([good, bad])
    assert both.steps == good.steps + bad.steps
    assert both.agreeing_steps == good.agreeing_steps
    assert both.route_agreement == bad.route_agreement and not both.ok
    assert merge([good, good]).ok
