"""The check that decides ``correct``: the readings count what they say,
a clean run passes, and a run with the timed path broken underneath (or
the float8 control in the program's place) comes out not correct.  The
reference itself is tested with its architecture plug-in
(``test_chipbench_arch_granitemoe.py``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import driver, yardstick


def test_readings_by_hand():
    ref = yardstick.RefOut(logits=np.array([[3.0, 1.0, -1.0, 1.0],
                                            [3.0, 1.0, -1.0, 1.0]]),
                           routing={},
                           router={0: np.array([[4., 3., 2., 1.]] * 2)})
    steps = yardstick.step_readings(
        ref, np.array([1, 0]), [np.array([3., 1., -1., 1.]),
                                np.array([3., 1., -1., 3.])],
        {0: np.array([[0, 2], [0, 1]])}, k=2)
    rms_l = np.sqrt(np.mean([9, 1, 1, 1]))
    rms_r = np.sqrt(np.mean([16, 9, 4, 1]))
    assert steps["token_gap"] == pytest.approx([2.0 / rms_l, 0.0])
    assert steps["logit_err"] == pytest.approx([0.0, 2.0 / np.sqrt(12)])
    assert steps["route_gap"] == pytest.approx([1.0 / rms_r, 0.0])
    r = yardstick.summarize([steps])
    assert r["token_gap"] == pytest.approx(2.0 / rms_l)
    assert r["logit_err"] == pytest.approx(1.0 / np.sqrt(12))
    assert r["route_gap_max"] == pytest.approx(1.0 / rms_r)
    ok, rows = yardstick.judge(r, {"token_gap": 1.0, "logit_err": 0.5,
                                   "route_gap": 0.5})
    assert ok is False and rows[0][0] == "token_gap"
    ok, rows = yardstick.judge(r, {"token_gap": None, "logit_err": 0.5,
                                   "route_gap": 0.5})
    assert ok is True and [n for n, _, _ in rows] == ["logit_err",
                                                      "route_gap"]


def _state_unchanged(h):
    """A decode step that returns the KV cache and position it got."""
    orig = h.engine.decode_batch

    def step(token, cache_list, pos, *a, **kw):
        kept = list(cache_list)
        tok, _, _ = orig(token, cache_list, pos, *a, **kw)
        return tok, kept, pos
    h.engine.decode_batch = step


def _token_altered(h):
    """The emitted token changed where the step produces it."""
    orig = h.engine.decode_batch

    def step(*a, **kw):
        tok, caches, pos = orig(*a, **kw)
        return (tok + 1) % h.cfg.vocab_size, caches, pos
    h.engine.decode_batch = step


def _half_batch(h):
    """Only the first half of a composed batch computed; the other rows
    get the first half's tokens."""
    orig = h.engine.decode_batch

    def step(token, *a, **kw):
        tok, caches, pos = orig(token, *a, **kw)
        b = tok.shape[0]
        if b > 1:
            half = (b + 1) // 2
            tok = jnp.concatenate([tok[:half], tok[:b - half]])
        return tok, caches, pos
    h.engine.decode_batch = step


@pytest.mark.parametrize("cell,fault,expect", [
    ("tiny.solo", None, True),
    ("tiny.batch", None, True),
    ("tiny.solo", _state_unchanged, False),
    ("tiny.solo", _token_altered, False),
    ("tiny.batch", _half_batch, False),
], ids=["clean-solo", "clean-batch", "state-unchanged", "token-altered",
        "half-batch"])
def test_broken_timed_path_is_not_correct(tmp_path, cell, fault, expect):
    res = chipbench_tiny.run(tmp_path, cell, fault=fault)
    assert res["correct"] is expect, res["checks"]
    assert res["checks"]["judged_steps"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_control_is_not_correct(tmp_path):
    """The float8 control in the program's place, on requests the
    program served, fails the limits the program passes."""
    out = chipbench_tiny.run(tmp_path, "tiny.solo", seed=11,
                             keep_served=True)
    res = out["result"]
    assert res["correct"] is True
    ctl = yardstick.summarize([
        yardstick.control_readings(out["plugin"], out["arch"], out["params"],
                                   s) for s in out["served"]])
    ok, _ = yardstick.judge(ctl, chipbench_tiny.LIMITS)
    assert not ok, ctl
    assert ctl["logit_err"] > 3 * res["checks"]["logit_err"]["value"]


def test_no_chip_means_no_result(tmp_path):
    spec_file = chipbench_tiny.build(tmp_path)
    with pytest.raises(driver.NoChip):
        driver.run("tiny.solo", 1, 1.0, False, lambda m: None, t_start=0.0,
                   base=tmp_path, spec_file=spec_file)
