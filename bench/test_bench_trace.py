"""The trace reduction on a hand-built trace (no profiler, no device)."""
import pytest

from bench import trace_reduce as tr

MS = 1_000_000        # ns


def trace():
    # window 0..100 ms; chip 0 busy 10-30 and 20-40 (overlap) and 90-95;
    # chip 1 busy 50-60 only
    return tr.Trace(
        ops={"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                               ("while.2", 20 * MS, 40 * MS),
                               ("fusion.1", 90 * MS, 95 * MS)],
             "/device:TPU:1": [("dot.3", 50 * MS, 60 * MS)]},
        modules={"/device:TPU:0": [("jit__predict_jit(7)", 10 * MS, 40 * MS),
                                   ("jit_zeros(2)", 90 * MS, 95 * MS)]},
        spans=[("bench.window", 0, 100 * MS),
               ("bench.ask", 0, 12 * MS),
               ("bench.build", 41 * MS, 89 * MS)],
        window=(0, 100 * MS))


def test_busy_union_counts_overlap_once():
    t = trace()
    assert tr.busy_s(t.ops["/device:TPU:0"], *t.window) == pytest.approx(0.035)
    assert tr.merged(t.ops["/device:TPU:0"]) == [(10 * MS, 40 * MS),
                                                 (90 * MS, 95 * MS)]


def test_busy_is_clipped_to_the_window():
    evs = [("a", -5 * MS, 5 * MS), ("b", 95 * MS, 120 * MS)]
    assert tr.busy_s(evs, 0, 100 * MS) == pytest.approx(0.010)


def test_idle_share_per_chip_and_mean_busy():
    t = trace()
    idle = tr.idle_shares(t)
    assert idle["/device:TPU:0"] == pytest.approx(0.65)
    assert idle["/device:TPU:1"] == pytest.approx(0.90)
    assert tr.mean_busy_s(t) == pytest.approx((0.035 + 0.010) / 2)
    assert t.window_s == pytest.approx(0.1)


def test_time_by_name_matches_program_names():
    t = trace()
    got = tr.time_by_name(t.modules["/device:TPU:0"], ("_predict_jit",),
                          *t.window)
    assert got == pytest.approx(0.030)


def test_top_ops_sum_calls_of_one_name():
    ops = tr.top_ops(trace())
    assert ops[0][0] in ("fusion.1", "while.2")
    assert dict((n, v) for n, v in ops)["fusion.1"] == pytest.approx(0.025)


def test_idle_gaps_are_labelled_by_the_host_span():
    gaps = tr.idle_gaps(trace())
    # no chip busy: 0-10, 40-50, 60-90, 95-100
    assert [round(g[1], 3) for g in gaps] == [0.03, 0.01, 0.01, 0.005]
    assert [g[0] for g in gaps] == ["bench.build", "bench.ask",
                                    "bench.build", "none"]


def test_op_name_keeps_the_instruction_name():
    text = "%fusion.12 = f32[8,1024]{1,0:T(8,128)} fusion(f32[8,1024] %p)"
    assert tr.op_name(text) == "fusion.12"
    assert tr.op_name("copy.3") == "copy.3"


def test_gp_mfu_divides_counted_work_by_the_gps_device_time():
    """30 ms of ``_predict_jit`` on the device for 3e9 counted operations:
    1e11 operations a second, over a 1e14 peak, is 0.1%."""
    from bench.harness import Recorder
    from bench.metrics import gp_device_ms, gp_mfu
    from bench.metrics_io import RunData

    rec = Recorder(deadline=1.0, traced=True)
    rec.gp_flops = [(0.01, 2e9), (0.05, 1e9), (0.5, 7e9)]
    rec.spans = [("ask", 0.0, 0.012), ("tell", 0.02, 0.03)]
    run = RunData(rec=rec, trace=trace(), lo=0.0, hi=0.1,
                  peaks={"flops_bf16": 1e14}, traffic={}, config={})
    assert gp_device_ms.read(run) == pytest.approx(30.0)
    assert gp_mfu.read(run) == pytest.approx(0.1)
    run.trace = None
    assert gp_mfu.read(run) is None
