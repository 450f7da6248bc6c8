"""The reduction of the program's ``odmoe.*`` spans on a small recorded
list of spans and device ops: self time, clipping to the window, the
share of loads the device hides, bytes over time, idle time by
innermost span, per-token division, and the readers on a real trace
file found under a temporary base."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import program_spans as P
from chipbench import spec
from chipbench import trace as T

MAIN, OTHER = (1, 0), (1, 1)

# One recorded tick, in ns: a decode step holding a router sync and a
# serve span; the serve span holds an expert load (with a gc inside it)
# and a wave.  A compile runs inside the tick before the step.
SPANS = [
    P.Span("odmoe.tick", 0, 1000, MAIN, {"step": 4}),
    P.Span("backend_compile_and_load", 20, 60, MAIN),
    P.Span("odmoe.decode_step", 100, 800, MAIN, {"rows": 1}),
    P.Span("odmoe.router_sync", 120, 80, MAIN, {"layer": 0}),
    P.Span("odmoe.serve", 200, 500, MAIN, {"layer": 0}),
    P.Span("odmoe.expert_load", 250, 100, MAIN,
           {"nbytes": 300, "predicted": 1}),
    P.Span("odmoe.gc", 300, 20, MAIN, {"generation": 2}),
    P.Span("odmoe.expert_load", 400, 100, MAIN,
           {"nbytes": 500, "predicted": 0}),
    P.Span("odmoe.wave", 550, 100, MAIN, {"experts": 2}),
    # another thread's span over the serve span is not its child
    P.Span("odmoe.expert_load", 600, 50, OTHER, {"nbytes": 100}),
]
# the device runs [150, 200), [280, 330) and [600, 700)
OPS = [T.Ev("%fusion.1", 150, 50), T.Ev("%copy.2", 280, 50),
       T.Ev("%moe_ffn_kernel.1", 600, 100)]


def test_self_time_less_nested_children():
    # serve 500 less loads [250, 350) + [400, 500) and wave [550, 650);
    # the other thread's load does not count
    assert P.self_ns(SPANS, "odmoe.serve", 0, 1000) == 500 - 300
    # a load's own gc is its child
    assert P.self_ns(SPANS, "odmoe.expert_load", 0, 1000) == \
        80 + 100 + 50
    assert P.self_ns(SPANS, "odmoe.decode_step", 0, 1000) == 800 - 580


def test_window_clipping():
    assert P.total_ns(SPANS, "odmoe.expert_load", 0, 1000) == 250
    assert P.total_ns(SPANS, "odmoe.expert_load", 300, 450) == 50 + 50
    assert P.self_ns(SPANS, "odmoe.serve", 300, 600) == \
        300 - 50 - 100 - 50
    assert P.union_ns(SPANS, ("odmoe.router_sync", "odmoe.serve"),
                      0, 250) == 130
    assert [s.start_ns for s in P.in_window(SPANS, 560, 590)] == \
        [0, 100, 200, 550]


def test_hidden_share_against_known_busy_intervals():
    busy = P.Busy(OPS, 0, 1000)
    assert busy.within(0, 1000) == 200
    assert busy.within(175, 300) == 25 + 20
    # loads' union [250, 350) + [400, 500) + [600, 650): busy 50 + 0 + 50
    assert P.busy_share(SPANS, "odmoe.expert_load", busy, 0, 1000) == \
        pytest.approx(100 * 100 / 250)
    assert P.busy_share(SPANS, "odmoe.model_clock", busy, 0,
                        1000) is None


def test_bytes_over_time():
    assert P.bytes_per_ns(SPANS, "odmoe.expert_load", "nbytes",
                          0, 1000) == pytest.approx(900 / 250)
    # only spans wholly inside the window count
    assert P.bytes_per_ns(SPANS, "odmoe.expert_load", "nbytes",
                          0, 520) == pytest.approx(800 / 200)
    assert P.bytes_per_ns(SPANS, "odmoe.expert_load", "nbytes",
                          0, 100) is None


def test_idle_by_innermost_span():
    busy = P.Busy(OPS, 0, 1000)
    idle = P.idle_by_label(SPANS, busy, -100, 1000)
    assert idle == {
        "none": 100,                        # [-100, 0)
        "odmoe.tick": 20 + 20 + 100,        # around the compile, the end
        "compile": 60,
        "odmoe.decode_step": 20 + 200,
        "odmoe.router_sync": 30,            # [120, 150)
        "odmoe.serve": 50 + 50 + 50,
        "odmoe.expert_load": 30 + 20 + 100,
        "odmoe.wave": 50,                   # [550, 600); then the other
    }                                       # thread's shorter load, busy
    assert sum(idle.values()) == 1100 - 200


def test_per_token_division():
    run = NS(counters={"decoded_tokens": 4})
    assert P.per_token_ms(run, 8e6) == pytest.approx(2.0)
    assert P.per_token_ms(NS(counters={"decoded_tokens": 0}), 8e6) is None


def test_none_without_program_spans():
    bench_only = [P.Span("backend_compile_and_load", 0, 10, MAIN)]
    assert P.in_window(bench_only, 0, 100) is None
    assert P.in_window(SPANS, 2000, 3000) is None


NEW = ("router_sync_ms_per_token", "wave_sched_ms_per_token",
       "wave_dispatch_ms_per_token", "expert_load_ms_per_token",
       "expert_load_hidden_share", "expert_load_gbps",
       "shadow_ms_per_token", "kv_copy_ms_per_token")


def _record(log_dir, program_spans: bool):
    """A CPU profiler trace with the harness's window span and, when
    asked, a few program spans inside it."""
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(log_dir)
    try:
        with TraceAnnotation(T.WINDOW_SPAN):
            if program_spans:
                with TraceAnnotation("odmoe.decode_step", rows=1):
                    with TraceAnnotation("odmoe.router_sync", layer=0):
                        pass
                    with TraceAnnotation("odmoe.expert_load", layer=0,
                                         expert=3, nbytes=4096,
                                         predicted=True):
                        pass
                with TraceAnnotation("odmoe.kv_gather", rows=1):
                    pass
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("program_spans", [True, False])
def test_readers_find_the_trace_under_the_base(tmp_path, monkeypatch,
                                               program_spans):
    log_dir = tmp_path / ".cache" / "trace" / "tiny.solo"
    _record(str(log_dir), program_spans)
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path)
    f = P.xplane_file("tiny.solo")
    assert f is not None and f.startswith(str(log_dir))
    assert P.xplane_file("tiny.batch") is None
    run = NS(cell=NS(name="tiny.solo"), trace=T.load(str(log_dir)),
             counters={"decoded_tokens": 2})
    got = {n: spec.metric_reader(n)(run) for n in NEW}
    if not program_spans:
        assert got == dict.fromkeys(NEW)
        return
    spans = P.window_spans(run)
    load = next(s for s in spans if s.name == "odmoe.expert_load")
    assert load.args == {"layer": 0, "expert": 3, "nbytes": 4096,
                         "predicted": 1}
    step = next(s for s in spans if s.name == "odmoe.decode_step")
    assert load.thread == step.thread
    assert step.start_ns <= load.start_ns and load.end_ns <= step.end_ns
    assert got["router_sync_ms_per_token"] == pytest.approx(
        P.total_ns(spans, "odmoe.router_sync", *run.trace.window)
        * 1e-6 / 2)
    assert got["expert_load_gbps"] == pytest.approx(4096 / load.dur_ns)
    assert got["kv_copy_ms_per_token"] > 0
    assert got["expert_load_hidden_share"] is None     # no device traced
    assert got["shadow_ms_per_token"] == 0.0
    # a trace of another window is not this run's
    other = NS(cell=run.cell, counters=run.counters,
               trace=NS(window=(run.trace.window[0] + 1,
                                run.trace.window[1]), devices=[]))
    assert P.window_spans(other) is None
