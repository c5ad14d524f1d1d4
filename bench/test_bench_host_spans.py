"""The program's spans read from a trace: the span readers and the idle-gap
names on hand-built traces, the spans read back from a real profile, and a
whole traced run of the build-bound mix on the CPU."""
import threading
import time

import jax
import pytest

from bench import host_spans as hs
from bench import trace_reduce as tr
from bench.harness import Recorder
from bench.metrics_io import RunData
from bench.span_report import SPAN_METRICS
from bench.spec import Spec
from bench.testing import ROOT, make_tiny_root

MS = 1_000_000        # ns
LOOP, BOARD = 1, 2    # host lines: JHost's loop and a board's thread


def span(name, start_ms, end_ms, line=LOOP, **stats):
    return hs.Span(name, start_ms * MS, end_ms * MS, line,
                   tuple(stats.items()))


def trace():
    """Window 0-100 ms; chip busy 10-40 (the GP's predict program) and
    90-95.  One ask (0-45) with its pool, observe and a pick, a dispatch, a
    pull while the board builds (46-89), a tell, and a second ask."""
    t = tr.Trace(
        ops={"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                               ("while.2", 20 * MS, 40 * MS),
                               ("fusion.1", 90 * MS, 95 * MS)]},
        modules={"/device:TPU:0": [("jit__predict_jit(7)", 10 * MS, 40 * MS),
                                   ("jit_zeros(2)", 90 * MS, 95 * MS)]},
        spans=[("bench.window", 0, 100 * MS), ("bench.ask", 0, 45 * MS),
               ("bench.build", 47 * MS, 87 * MS)],
        window=(0, 100 * MS))
    t.program_spans = [
        span("jx.host.ask", 0, 45, n=4),
        span("jx.search.pool", 0, 8, rows=512),
        span("jx.search.observe", 8, 10, m=4),
        span("jx.gp.append", 8.5, 9.5, cap=1024, rows=4),
        span("jx.search.acquire", 10, 44),
        span("jx.gp.fit_y", 10, 12, cap=1024, rows=900),
        span("jx.gp.predict", 12, 44, cap=1024, rows=512),
        span("jx.gp.fetch", 14, 44, bytes=8192),
        span("jx.host.dispatch", 45, 46, n=4, cid=0),
        span("jx.host.pull", 46, 89, n_msgs=1),
        span("jx.host.tell", 89, 90, n=4, cid=0),
        span("jx.host.ask", 96, 99, n=4),
        span("jx.client.batch", 46, 88, BOARD, n=4, cid=0),
        span("jx.client.build", 47, 87, BOARD),
        span("jx.build.lower", 47, 55, BOARD, kind="prefill"),
        span("jx.build.compile", 55, 80, BOARD, kind="prefill"),
        span("jx.build.analyze", 80, 86, BOARD),
        span("jx.client.measure", 87, 88, BOARD, n=4),
    ]
    return t


def run_of(t):
    rec = Recorder(deadline=1.0, traced=True)
    return RunData(rec=rec, trace=t, lo=0.0, hi=0.1,
                   peaks={"flops_bf16": 1e14}, traffic={}, config={})


# ask_idle_ms: the first ask is idle 0-10 and 40-45, the second 96-99;
# gp_host_ms: 35 ms of GP spans, 30 of them under the predict program
EXPECTED = {"ask_idle_ms": 9.0, "gp_host_ms": 2.5, "pool_ms": 4.0,
            "acquire_ms": 17.0, "pull_wait_share": 43.0,
            "build_lower_s": 0.008, "build_compile_s": 0.025,
            "build_analyze_s": 0.006}


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_each_span_reader_on_a_hand_built_trace(metric):
    got = Spec(ROOT).reader(metric)(run_of(trace()))
    assert got == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_readers_find_nothing_without_program_spans(metric):
    """A trace of a program that opens no spans (or none at all) gives no
    value, never 0."""
    t = trace()
    del t.program_spans
    read = Spec(ROOT).reader(metric)
    assert read(run_of(t)) is None
    assert read(run_of(None)) is None


def test_idle_gaps_name_the_innermost_span_on_each_thread():
    gaps = hs.idle_gaps(trace())
    # no chip busy: 40-90, 0-10, 95-100
    assert [round(g[1], 3) for g in gaps] == [0.05, 0.01, 0.005]
    assert [g[0] for g in gaps] == ["jx.host.pull|jx.build.compile",
                                    "jx.search.pool", "jx.host.ask"]
    # the durations are the harness's
    assert [g[1] for g in gaps] == [g[1] for g in tr.idle_gaps(trace())]


def test_a_gap_inside_a_fetch_is_named_by_the_fetch():
    """The harness's span around the ask covers the gap too; the program's
    innermost span names it."""
    t = tr.Trace(ops={"/device:TPU:0": [("a", 0, 5 * MS),
                                        ("b", 20 * MS, 25 * MS)]},
                 modules={}, window=(0, 25 * MS),
                 spans=[("bench.window", 0, 25 * MS),
                        ("bench.ask", 0, 25 * MS)])
    t.program_spans = [span("jx.host.ask", 0, 25),
                       span("jx.search.acquire", 3, 24),
                       span("jx.gp.predict", 4, 23),
                       span("jx.gp.fetch", 6, 19)]
    assert tr.idle_gaps(t)[0][0] == "bench.ask"
    assert hs.idle_gaps(t)[0][0] == "jx.gp.fetch"


def test_a_gap_no_program_span_covers_keeps_the_harness_label():
    t = trace()
    t.program_spans = []
    assert hs.idle_gaps(t) == tr.idle_gaps(t)


def test_own_seconds_leave_out_the_spans_inside():
    own = hs.own_seconds(trace().program_spans)
    assert own["jx.host.ask"] == pytest.approx(0.045 - 0.008 - 0.002 - 0.034
                                               + 0.003)
    assert own["jx.gp.predict"] == pytest.approx(0.002)
    assert own["jx.client.build"] == pytest.approx(0.001)


def test_spans_are_read_back_from_a_profile_with_their_thread(tmp_path):
    def board():
        with jax.profiler.TraceAnnotation("jx.client.batch", n=2, cid=7):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("jx.host.dispatch", n=2, cid=7):
            time.sleep(0.001)
        t = threading.Thread(target=board)
        t.start()
        t.join()
        with jax.profiler.TraceAnnotation("bench.ask"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = {s.name: s for s in hs.read_spans(str(tmp_path))}
    assert set(spans) == {"jx.host.dispatch", "jx.client.batch"}
    host, board_span = spans["jx.host.dispatch"], spans["jx.client.batch"]
    assert host.line != board_span.line
    assert dict(host.stats)["cid"] == dict(board_span.stats)["cid"] == 7
    assert board_span.end - board_span.start >= 2 * MS


def test_a_traced_run_of_the_build_bound_mix_reads_its_spans(tmp_path):
    """On the CPU, at the tiny size: every build's lowering, compile and
    analysis lie inside the harness's time for it."""
    from bench import span_report

    root = make_tiny_root(str(tmp_path / "tiny"))
    out = span_report.report(root, "tiny.random", 11, 4.0,
                             require_chip=False, peaks_kind="TPU v5 lite")
    assert out["correct"]
    m = out["span_metrics"]
    for name in ("ask_idle_ms", "pull_wait_share", "build_lower_s",
                 "build_compile_s", "build_analyze_s"):
        assert m[name] is not None and m[name] > 0, name
    parts = m["build_lower_s"] + m["build_compile_s"] + m["build_analyze_s"]
    assert 0.9 * out["metrics"]["build_s"] <= parts <= out["metrics"][
        "build_s"]
    assert out["span_counts"]["jx.client.build"] == out["builds"] > 0


def test_gap_parts_give_the_seconds_under_each_span():
    parts = hs.gap_parts(trace().program_spans, 0, 10 * MS)
    assert parts == pytest.approx({"jx.host.ask": 0.010,
                                   "jx.search.pool": 0.008,
                                   "jx.search.observe": 0.002,
                                   "jx.gp.append": 0.001})
