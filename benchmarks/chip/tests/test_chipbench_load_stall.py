"""The readers of the decode thread's load stall and of the share of
fetches made ahead, on small recorded lists of spans: every load on the
decode thread (no joins), loads on loader threads joined by the decode
thread, inline fetches inside a join, and a window with no program
span."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import program_spans as P
from chipbench import spec

MAIN, LOADER, LOADER2 = (1, 0), (1, 1), (1, 2)
STEP = P.Span("odmoe.decode_step", 100, 800, MAIN, {"rows": 1})
SERVE = P.Span("odmoe.serve", 200, 500, MAIN, {"layer": 0})

# every load on the decode thread, no joins, no ``ahead`` argument
PARENT = [STEP, SERVE,
          P.Span("odmoe.expert_load", 250, 100, MAIN,
                 {"nbytes": 300, "predicted": 1}),
          P.Span("odmoe.gc", 300, 20, MAIN, {"generation": 2}),
          P.Span("odmoe.expert_load", 400, 100, MAIN,
                 {"nbytes": 500, "predicted": 0})]

# two fetches ahead on loader threads, one join on the decode thread
# [250, 300) and one inline demand fetch [400, 500)
CHANGE = [STEP, SERVE,
          P.Span("odmoe.expert_load", 0, 200, LOADER,
                 {"nbytes": 300, "predicted": 1, "ahead": 1}),
          P.Span("odmoe.expert_load", 150, 110, LOADER2,
                 {"nbytes": 300, "predicted": 1, "ahead": 1}),
          P.Span("odmoe.expert_wait", 250, 50, MAIN,
                 {"experts": 2, "ready": 1}),
          P.Span("odmoe.expert_load", 400, 100, MAIN,
                 {"nbytes": 500, "predicted": 0, "ahead": 0})]

# the inline oracle: the fetches run inside the join, on the decode
# thread, and count once
SYNC = [STEP, SERVE,
        P.Span("odmoe.expert_wait", 250, 200, MAIN,
               {"experts": 2, "ready": 0}),
        P.Span("odmoe.expert_load", 260, 80, MAIN,
               {"nbytes": 300, "predicted": 1, "ahead": 1}),
        P.Span("odmoe.expert_load", 350, 90, MAIN,
               {"nbytes": 300, "predicted": 1, "ahead": 1})]


def _read(monkeypatch, spans, *names):
    run = NS(trace=NS(window=(0, 1000)), counters={"decoded_tokens": 2})
    monkeypatch.setattr(P, "window_spans",
                        lambda r: P.in_window(spans, *r.trace.window))
    return [spec.metric_reader(n)(run) for n in names]


def test_parent_shape(monkeypatch):
    stall, share, loads = _read(monkeypatch, PARENT,
                                "load_stall_ms_per_token",
                                "loads_ahead_share",
                                "expert_load_ms_per_token")
    assert stall == pytest.approx(200 * 1e-6 / 2)
    assert stall == pytest.approx(loads)
    assert share == 0.0


def test_change_shape(monkeypatch):
    stall, share, loads = _read(monkeypatch, CHANGE,
                                "load_stall_ms_per_token",
                                "loads_ahead_share",
                                "expert_load_ms_per_token")
    # the loader threads' fetches count only through the join
    assert stall == pytest.approx((50 + 100) * 1e-6 / 2)
    assert share == pytest.approx(100 * 2 / 3)
    assert loads == pytest.approx((200 + 110 + 100) * 1e-6 / 2)


def test_fetches_inside_a_join_count_once(monkeypatch):
    stall, share = _read(monkeypatch, SYNC, "load_stall_ms_per_token",
                         "loads_ahead_share")
    assert stall == pytest.approx(200 * 1e-6 / 2)
    assert share == 100.0


def test_none_without_program_spans(monkeypatch):
    bench_only = [P.Span("backend_compile_and_load", 0, 10, MAIN)]
    assert _read(monkeypatch, bench_only, "load_stall_ms_per_token",
                 "loads_ahead_share") == [None, None]
