"""The program's host spans (``repro.core.tracing``) in a profiler trace.

A short ParEGO sweep with the device GP runs through ``JHost.explore`` on
the loopback fleet, building a reduced Mamba-2 on the board's thread, under
``jax.profiler``; the trace must hold every span the benchmark's readers
match, nested as the layers nest, with the batch's config id shared between
the host's dispatch and the board's batch.
"""
import collections
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.core import ExploreService, JConfig, JHost, ResultStore
from repro.core.search import gp_jax
from repro.core.search.bayesopt import BayesOpt
from repro.core.search.gp_jax import JaxIncrementalGP
from repro.core.tracing import span
from repro.launch import explore
from tests.test_tenancy import Replay, knob_list, start_fleet, stop_fleet

Span = collections.namedtuple("Span", "name start end line stats")

# the GP's programs as bench/metrics/gp_device_ms.py finds them among the
# device trace's XLA modules
GP_PROGRAMS = ("_append_jit", "_refactor_jit", "_rethin_jit", "_fit_y_jit",
               "_predict_jit", "_predict_mean_jit", "_ehvi_jit")


def read_spans(trace_dir):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [Span(ev.name, ev.start_ns, ev.end_ns, (plane.name, i),
                             dict(ev.stats))
                        for ev in line.events if ev.name.startswith("jx.")]
    return out


def inside(span, outer_names, spans):
    """True where a span named in ``outer_names`` holds ``span`` on its
    thread."""
    return any(o.name in outer_names and o.line == span.line
               and o.start <= span.start and span.end <= o.end
               for o in spans if o is not span)


@pytest.fixture(scope="module")
def sweep_spans(tmp_path_factory):
    args = explore.parse_args([
        "--workload", "mamba2-780m", "--reduced", "--shape", "generate",
        "--samples", "20", "--algorithm", "bayesopt", "--gp", "jax",
        "--clients", "1", "--batch-size", "4", "--prompt-len", "16",
        "--gen-tokens", "8", "--timeout", "300"])
    space = explore.generation_space(reduced(get_arch(args.workload)), 1)
    jc = JConfig(space, n_chips=1)
    pair, _, _ = explore.start_fleet(args, jc, explore.make_build_fn(args, jc))
    host = JHost(pair.host(), ResultStore(knob_names=[k.name for k in space]),
                 timeout_s=args.timeout, poll_s=0.05)
    search = BayesOpt(space, seed=3, gp_mode="jax", hyper_refresh_every=8)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        store = host.explore(search, args.workload, "generate", args.samples,
                             batch_size=args.batch_size)
    finally:
        host.stop_clients()
        jax.profiler.stop_trace()
    assert sum(r.status == "ok" for r in store.records) == args.samples
    return read_spans(trace_dir)


def test_every_span_the_readers_match_is_in_the_trace(sweep_spans):
    names = {s.name for s in sweep_spans}
    assert {"jx.host.ask", "jx.host.dispatch", "jx.host.pull",
            "jx.host.tell", "jx.search.pool", "jx.search.observe",
            "jx.search.refresh", "jx.search.acquire", "jx.gp.append",
            "jx.gp.fit_y", "jx.gp.predict", "jx.gp.fetch",
            "jx.client.batch", "jx.client.build", "jx.client.measure",
            "jx.build.lower", "jx.build.compile",
            "jx.build.analyze"} <= names


def test_spans_nest_as_the_layers_do(sweep_spans):
    spans = sweep_spans
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name.rsplit(".", 1)[0]].append(s)
    for s in by["jx.search"]:
        assert inside(s, {"jx.host.ask"}, spans), s
    for s in by["jx.gp"]:
        assert inside(s, {"jx.search.observe", "jx.search.acquire",
                          "jx.search.refresh"}, spans), s
    for s in by["jx.build"]:
        assert inside(s, {"jx.client.build"}, spans), s
    for s in by["jx.client"]:
        if s.name != "jx.client.batch":
            assert inside(s, {"jx.client.batch"}, spans), s
    fetches = [s for s in spans if s.name == "jx.gp.fetch"]
    assert fetches and all(inside(s, {"jx.gp.predict", "jx.gp.append"},
                                  spans) for s in fetches)
    # the host loop and the board are two threads: two host lines
    host_lines = {s.line for s in by["jx.host"]}
    board_lines = {s.line for s in by["jx.client"]}
    assert len(host_lines) == 1 and not host_lines & board_lines


def test_spans_carry_their_stats(sweep_spans):
    first = {}
    for s in sweep_spans:
        first.setdefault(s.name, s.stats)
    assert first["jx.host.ask"]["n"] == 4
    assert first["jx.host.pull"]["n_msgs"] >= 0
    assert first["jx.search.pool"]["rows"] > 0
    assert first["jx.gp.predict"]["cap"] >= 16
    assert first["jx.gp.predict"]["rows"] == first["jx.search.pool"]["rows"]
    assert first["jx.gp.fetch"]["bytes"] > 0
    kinds = {s.stats["kind"] for s in sweep_spans
             if s.name.startswith("jx.build.") and "kind" in s.stats}
    assert kinds == {"prefill", "decode"}


def test_the_parego_ask_stages_its_picks_inside_acquire(sweep_spans):
    """Each ParEGO ask stages its four picks over the pool, inside a
    ``jx.search.acquire`` span, and every pick's fit and predict is served
    from the stage."""
    stages = [s for s in sweep_spans if s.name == "jx.gp.stage"]
    assert stages
    for s in stages:
        assert inside(s, {"jx.search.acquire"}, sweep_spans), s
        assert s.stats["targets"] == 4 and s.stats["cap"] >= 16
    pools = {s.stats["rows"] for s in sweep_spans
             if s.name == "jx.search.pool"}
    assert {s.stats["rows"] for s in stages} <= pools
    picks = [s for s in sweep_spans if s.name in ("jx.gp.fit_y",
                                                  "jx.gp.predict")]
    assert len(picks) == 8 * len(stages)
    assert all(s.stats["staged"] == 1 for s in picks)


def test_the_analysis_span_counts_the_layer_loop_per_trip(sweep_spans):
    """Each build's analysis records the trips it applied: the reduced
    Mamba-2's two scanned layers, and in prefill the SSD's scan over the
    16-token prompt's two chunks of 8; no loop counted once for want of a
    trip count, and no wire bytes on one chip."""
    stats = [s.stats for s in sweep_spans if s.name == "jx.build.analyze"]
    assert stats
    for st in stats:
        assert (st["pre_trips"], st["dec_trips"]) == (2 + 2, 2)
        assert st["pre_unknown_trip_loops"] == st["dec_unknown_trip_loops"] == 0
        assert not [k for k in st if "_wire_" in k]


def test_a_batch_shares_its_config_id_between_host_and_board(sweep_spans):
    def cids(name):
        return sorted(s.stats["cid"] for s in sweep_spans if s.name == name)

    sent = cids("jx.host.dispatch")
    assert sent and sent == cids("jx.client.batch")
    assert {s.stats["n"] for s in sweep_spans
            if s.name == "jx.host.dispatch"} == {4}


def test_a_two_tenant_service_spans_its_loop(tmp_path):
    """Two tenants under one ``ExploreService``: the loop emits every
    ``jx.host.*`` span, with its arguments, on the thread that runs it."""
    sizes = {"a": 12, "b": 9}
    pair, threads = start_fleet(2)
    svc = ExploreService(pair.host(), timeout_s=60.0, poll_s=0.005,
                         batch_size=3, checkpoint_root=str(tmp_path / "ck"),
                         checkpoint_every=4)
    for seed, (name, n) in enumerate(sizes.items()):
        svc.submit_sweep(name, Replay(knob_list(seed, n)), "tpu_v4", "8", n)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with span("jx.test.loop"):
            svc.run()
    finally:
        jax.profiler.stop_trace()
        stop_fleet(pair, threads)
    spans = read_spans(trace_dir)
    host = [s for s in spans if s.name.startswith("jx.host.")]
    assert {s.name for s in host} == {"jx.host.ask", "jx.host.dispatch",
                                      "jx.host.pull", "jx.host.tell",
                                      "jx.host.checkpoint"}
    assert all(inside(s, {"jx.test.loop"}, spans) for s in host)

    def stats(name):
        return [s.stats for s in host if s.name == name]

    n = sum(sizes.values())
    assert sum(st["n"] for st in stats("jx.host.ask")) == n
    assert sum(st["n"] for st in stats("jx.host.dispatch")) == n
    assert sum(st["n"] for st in stats("jx.host.tell")) == n
    assert sum(st["n_msgs"] for st in stats("jx.host.pull")) >= n
    sent = [st["cid"] for st in stats("jx.host.dispatch")]
    assert len(set(sent)) == len(sent) and set(sent) <= set(range(n))
    assert {st["cid"] for st in stats("jx.host.tell")} <= set(range(n))


def test_the_gps_other_programs_are_spanned(tmp_path):
    """Thinning to the inducing set, the refactor it runs, and the fused
    EHVI score each open their span, with the fetch of the score inside."""
    rng = np.random.default_rng(0)
    gp = JaxIncrementalGP(inducing_threshold=16)
    gp.observe(rng.random((16, 3)))
    gp.fit_y_multi(rng.random((16, 2)))
    trace_dir = str(tmp_path)
    jax.profiler.start_trace(trace_dir)
    try:
        gp.score_ehvi(rng.random((8, 3)), np.array([[0.2, 0.8], [0.6, 0.3]]),
                      np.array([1.0, 1.0]))
        gp.observe(rng.random((8, 3)))
    finally:
        jax.profiler.stop_trace()
    spans = read_spans(trace_dir)
    names = {s.name for s in spans}
    assert {"jx.gp.score_ehvi", "jx.gp.append", "jx.gp.rethin",
            "jx.gp.refactor", "jx.gp.fetch"} <= names
    for s in spans:
        if s.name == "jx.gp.refactor":
            assert inside(s, {"jx.gp.rethin"}, spans)
    assert any(inside(s, {"jx.gp.score_ehvi"}, spans) for s in spans
               if s.name == "jx.gp.fetch")


def test_gp_programs_keep_the_names_the_device_metrics_match():
    """The device trace names a program by its XLA module, ``jit_`` and the
    jitted function's name: renaming a GP program must fail here, not
    silently empty ``gp_device_ms``."""
    jitted = {n for n, f in vars(gp_jax).items()
              if n.endswith("_jit") and hasattr(f, "lower")}
    assert jitted == set(GP_PROGRAMS)
    rng = np.random.default_rng(1)
    gp = JaxIncrementalGP()
    gp.observe(rng.random((8, 3)))
    gp.fit_y(rng.random(8))
    xq, _ = gp._pad_pool(rng.random((4, 3)))
    with jax.enable_x64(True):
        text = gp_jax._predict_jit.lower(
            gp._xb, gp._lib, gp._alpha1, np.int32(gp._n), xq, gp.ls,
            gp.signal).as_text()
    assert text.splitlines()[0].startswith("module @jit__predict_jit")
    for name in GP_PROGRAMS:
        assert getattr(gp_jax, name).__name__ == name
