"""Fair-share multi-tenancy (PR 10): DWRR deficit-weighted pick order under
unequal per-config costs, weighted shares, priority preemption of queue
position (never of in-flight work), per-tenant in-flight caps, quarantine-
storm isolation via caps, and the ExploreService loop — per-tenant results
bit-identical to solo runs over the same loopback fleet, subscribe streams,
cancel, and the SUBMIT/STATUS/CANCEL control verbs."""
import os
import shutil
import tempfile
import threading

import numpy as np
import pytest

from repro.core import (DispatchScheduler, ExploreService, JClient, JConfig,
                        JHost, ResultStore, TestConfig, transport)
from repro.core.durable import (ExplorationCheckpoint, restore_sweep,
                                tenant_checkpoint_dir)
from repro.core.space import DesignSpace, KIND_HW, KIND_SW, Knob
from repro.core.transport import (CONTROL_CANCEL, CONTROL_STATUS,
                                  CONTROL_SUBMIT, is_control_msg)
from repro.roofline.analysis import Artifact


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def tc(i):
    return TestConfig(i, "a", "s", {"x": i})


def ok(cid, client):
    return {"config_id": cid, "status": "ok", "client_id": client,
            "metrics": {"time_s": 1.0}, "cached": False, "wall_s": 0.0}


def answer(sched, client, cfgs):
    for c in cfgs:
        sched.on_result(ok(c.config_id, client))


# ---------------------------------------------------------------------------
# DWRR invariants (fake clock, no transports)
# ---------------------------------------------------------------------------


def test_single_tenant_back_compat():
    """No add_tenant call: everything rides the implicit default tenant and
    the legacy ``pending`` view stays a live deque."""
    s = DispatchScheduler([0], batch_size=2, clock=FakeClock())
    for i in range(4):
        s.submit(tc(i))
    assert len(s.pending) == 4
    d = s.next_dispatches()
    assert [(c, len(cfgs)) for c, cfgs in d] == [(0, 2)]
    assert all(ch.tenant == "default" for ch in s.chunks.values())
    assert "tenants" not in s.stats()          # single tenant: format unchanged


def test_deficit_pick_order_unequal_costs():
    """Equal weights, 3x observed cost difference: once the per-tenant cost
    EWMAs are learned, the cheap tenant gets 3x the picks — fleet *time*
    (not config count) splits evenly."""
    clk = FakeClock()
    s = DispatchScheduler([0], batch_size=1, clock=clk)
    s.add_tenant("slow")
    s.add_tenant("fast")
    cost = {"slow": 3.0, "fast": 1.0}
    for i in range(8):
        s.submit(tc(i), tenant="slow")
    for i in range(100, 108):
        s.submit(tc(i), tenant="fast")
    picks = []
    for _ in range(12):
        d = s.next_dispatches()
        assert len(d) == 1
        client, cfgs = d[0]
        tenant = s.tenant_of(cfgs[0].config_id)
        picks.append(tenant)
        clk.advance(cost[tenant] * len(cfgs))   # the config's true cost
        answer(s, client, cfgs)
    assert s.tenants["slow"].cost_ewma == pytest.approx(3.0)
    assert s.tenants["fast"].cost_ewma == pytest.approx(1.0)
    # past the 1.0-seeded warmup the ratio locks to 3 picks of fast per
    # pick of slow: 6s of fleet time each over the steady-state window
    tail = picks[4:]
    assert tail.count("fast") == 6 and tail.count("slow") == 2


def test_weighted_share():
    """weight=3 vs weight=1 at equal (1s) cost: 3x the picks."""
    clk = FakeClock()
    s = DispatchScheduler([0], batch_size=1, clock=clk)
    s.add_tenant("big", weight=3.0)
    s.add_tenant("small", weight=1.0)
    for i in range(12):
        s.submit(tc(i), tenant="big")
    for i in range(100, 112):
        s.submit(tc(i), tenant="small")
    picks = []
    for _ in range(8):
        d = s.next_dispatches()
        client, cfgs = d[0]
        picks.append(s.tenant_of(cfgs[0].config_id))
        clk.advance(1.0)
        answer(s, client, cfgs)
    assert picks.count("big") == 6 and picks.count("small") == 2


def test_priority_preempts_queue_position_not_inflight():
    clk = FakeClock()
    s = DispatchScheduler([0], batch_size=2, clock=clk)
    s.add_tenant("lo", priority=0)
    for i in range(6):
        s.submit(tc(i), tenant="lo")
    d = s.next_dispatches()
    assert s.tenant_of(d[0][1][0].config_id) == "lo"
    inflight_before = set(s.inflight)
    # a high-priority tenant arrives while lo's chunk is in flight
    s.add_tenant("hi", priority=5)
    for i in range(100, 102):
        s.submit(tc(i), tenant="hi")
    # never revoked: the dispatched lo chunk keeps flying
    assert set(s.inflight) == inflight_before
    assert s.next_dispatches() == []           # eager depth-1: slot is busy
    answer(s, 0, d[0][1])
    # ...but the freed slot serves hi first, though lo queued earlier
    d2 = s.next_dispatches()
    assert s.tenant_of(d2[0][1][0].config_id) == "hi"
    answer(s, 0, d2[0][1])
    d3 = s.next_dispatches()                   # hi drained: lo resumes
    assert s.tenant_of(d3[0][1][0].config_id) == "lo"


def test_per_tenant_inflight_cap_bounds_dispatch():
    s = DispatchScheduler([0, 1], batch_size=4, clock=FakeClock())
    s.add_tenant("capped", max_inflight=3)
    for i in range(8):
        s.submit(tc(i), tenant="capped")
    d = s.next_dispatches()
    # chunk clipped to headroom, second client gets nothing: cap reached
    assert sum(len(cfgs) for _, cfgs in d) == 3
    assert s.tenants["capped"].inflight == 3
    client, cfgs = d[0]
    answer(s, client, cfgs)
    assert s.tenants["capped"].inflight == 0
    d2 = s.next_dispatches()
    assert sum(len(cfgs) for _, cfgs in d2) == 3


def test_quarantine_storm_isolation():
    """A capped tenant whose configs always hang can quarantine at most the
    clients its cap lets it occupy — the well-behaved tenant finishes
    untouched on the remaining client."""
    clk = FakeClock()
    s = DispatchScheduler([0, 1], batch_size=2, timeout_s=10.0,
                          max_retries=1, clock=clk)
    s.add_tenant("storm", max_inflight=2)
    s.add_tenant("good")
    for i in range(8):
        s.submit(tc(i), tenant="storm")
    for i in range(100, 104):
        s.submit(tc(i), tenant="good")
    d = s.next_dispatches()
    by_tenant = {s.tenant_of(cfgs[0].config_id): (client, cfgs)
                 for client, cfgs in d}
    assert set(by_tenant) == {"storm", "good"}
    storm_client = by_tenant["storm"][0]
    good_client = by_tenant["good"][0]
    # good answers promptly; storm hangs at its cap so it cannot spread
    done = []
    answer(s, good_client, by_tenant["good"][1])
    done += by_tenant["good"][1]
    while len(done) < 4:
        d2 = s.next_dispatches()
        assert d2, "good tenant starved behind the storm"
        for client, cfgs in d2:
            assert s.tenant_of(cfgs[0].config_id) == "good"
            assert client == good_client       # storm's client is occupied
            answer(s, client, cfgs)
            done += cfgs
    # the storm finally times out (deadline = timeout_s x chunk size):
    # only its own client is quarantined
    clk.advance(25.0)
    expired = s.expire()
    assert storm_client in s.quarantined
    assert good_client not in s.quarantined
    assert all(s.tenant_of(t.config_id) == "storm" for t, _ in expired)
    # retries rejoined storm's own queue, not anyone else's
    assert all(s.tenant_of(t.config_id) == "storm"
               for t, _ in s.tenants["storm"].queue)
    assert not s.tenants["good"].queue and s.tenants["good"].inflight == 0


def test_reactivation_clamp():
    """An idle tenant returning after a long absence competes from the
    most-favoured active balance (fair alternation), not from banked
    credit (which would let it monopolize the fleet)."""
    clk = FakeClock()
    s = DispatchScheduler([0], batch_size=1, clock=clk)
    s.add_tenant("a")
    s.add_tenant("b")
    for i in range(4):
        s.submit(tc(i), tenant="a")
    for _ in range(4):                          # a burns deficit alone
        d = s.next_dispatches()
        clk.advance(1.0)
        answer(s, d[0][0], d[0][1])
    assert s.tenants["a"].deficit == pytest.approx(-4.0)
    assert s.tenants["b"].deficit == 0.0
    for i in range(100, 103):                   # keep a active...
        s.submit(tc(i), tenant="a")
    for i in range(200, 203):                   # ...then b wakes up: clamped
        s.submit(tc(i), tenant="b")
    assert s.tenants["b"].deficit == pytest.approx(s.tenants["a"].deficit)
    # from the clamped balance on, service alternates fairly — without the
    # clamp b's four banked credits would win four straight picks
    picks = []
    for _ in range(4):
        d = s.next_dispatches()
        picks.append(s.tenant_of(d[0][1][0].config_id))
        clk.advance(1.0)
        answer(s, d[0][0], d[0][1])
    assert picks == ["a", "b", "a", "b"]


def test_cancel_tenant_drops_queue_and_orphans_inflight():
    s = DispatchScheduler([0], batch_size=2, clock=FakeClock())
    s.add_tenant("t")
    for i in range(6):
        s.submit(tc(i), tenant="t")
    d = s.next_dispatches()
    flying = [c.config_id for c in d[0][1]]
    n_queued, n_orphaned = s.cancel_tenant("t")
    assert (n_queued, n_orphaned) == (4, 2)
    assert not s.tenants["t"].queue and s.tenants["t"].inflight == 0
    # the orphaned answers ride the duplicate path: no crash, no record
    for cid in flying:
        assert s.on_result(ok(cid, 0)) is None
    assert not s.busy()


# ---------------------------------------------------------------------------
# ExploreService over a loopback fleet
# ---------------------------------------------------------------------------


def small_space():
    return DesignSpace([
        Knob("clock_scale", (0.5, 1.0), KIND_HW),
        Knob("blk", (0, 1, 2, 3), KIND_SW),
    ])


def toy_artifact(f=5e12, n_dev=8):
    return Artifact(flops_per_device=f, bytes_per_device=2e10,
                    wire_bytes_per_device=1e8, collectives={},
                    arg_bytes=10 ** 9, temp_bytes=10 ** 8,
                    output_bytes=10 ** 6, n_devices=n_dev)


def make_build(jc):
    def build(tc):
        h = hash(jc.cache_key(tc)) % 7 + 1
        return toy_artifact(5e12 * h), {
            "decode_artifact": toy_artifact(1e11 * h),
            "n_decode_tokens": 10}
    return build


class Replay:
    """Deterministic ask/tell: hands out a fixed knob list in order."""

    def __init__(self, ks):
        self._k = list(ks)

    def ask(self, n):
        out, self._k = self._k[:n], self._k[n:]
        return out

    def tell(self, knobs, y):
        pass


def knob_list(seed, n):
    rng = np.random.default_rng(seed)
    return small_space().sample_batch(rng, n)


def result_key(store, objectives=("time_s", "power_w")):
    return sorted(
        (r.config_id,) + tuple(float(r.metrics[k]).hex() for k in objectives)
        for r in store.records if r.status == "ok")


def start_fleet(n_clients):
    pair = transport.LoopbackPair(n_clients)
    space = small_space()
    jc = JConfig(space, n_chips=8)
    threads = []
    for i in range(n_clients):
        c = JClient(jc, make_build(jc), transport=pair.client(i), client_id=i)
        t = threading.Thread(target=c.serve, kwargs=dict(poll_s=0.005),
                             daemon=True)
        t.start()
        threads.append(t)
    return pair, threads


def stop_fleet(pair, threads):
    for i in range(len(threads)):
        pair.host().push(i, {"cmd": "stop"})
    for t in threads:
        t.join(timeout=10.0)


def test_service_results_bit_identical_to_solo():
    """K=2 interleaved sweeps on one shared fleet produce, per tenant,
    exactly the records a solo JHost run of the same sweep produces."""
    knobs_a = knob_list(0, 12)
    knobs_b = knob_list(1000, 9)
    solo = {}
    for name, ks in (("a", knobs_a), ("b", knobs_b)):
        pair, threads = start_fleet(2)
        host = JHost(pair.host(), ResultStore(), timeout_s=60.0, poll_s=0.005)
        store = host.explore(Replay(ks), "tpu_v4", "8", len(ks),
                             batch_size=3, dispatch="pipelined")
        stop_fleet(pair, threads)
        solo[name] = result_key(store)

    pair, threads = start_fleet(2)
    svc = ExploreService(pair.host(), timeout_s=60.0, poll_s=0.005,
                         batch_size=3, dispatch="pipelined")
    sa = svc.submit_sweep("a", Replay(knobs_a), "tpu_v4", "8", len(knobs_a))
    sb = svc.submit_sweep("b", Replay(knobs_b), "tpu_v4", "8", len(knobs_b),
                          weight=2.0)
    svc.run()                                   # returns when both are done
    stop_fleet(pair, threads)
    assert sa.state == "done" and sb.state == "done"
    assert result_key(sa.store) == solo["a"]
    assert result_key(sb.store) == solo["b"]
    # tenant-local id spaces: each store saw ids 0..n-1, not global ids
    assert {r.config_id for r in sa.store.records} == set(range(len(knobs_a)))
    assert {r.config_id for r in sb.store.records} == set(range(len(knobs_b)))


def test_service_subscribe_streams_and_cancel_ends():
    knobs = knob_list(7, 20)
    pair, threads = start_fleet(1)
    svc = ExploreService(pair.host(), timeout_s=60.0, poll_s=0.005,
                         batch_size=2)
    svc.submit_sweep("t", Replay(knobs), "tpu_v4", "8", len(knobs))
    seen = []

    def consume():
        for rec in svc.subscribe("t", timeout_s=30.0):
            seen.append(rec)

    sub = threading.Thread(target=consume, daemon=True)
    sub.start()
    svc.run()
    sub.join(timeout=10.0)
    stop_fleet(pair, threads)
    assert not sub.is_alive()                   # _DONE closed the stream
    assert len(seen) == len(knobs)
    assert [r.config_id for r in seen] == sorted(r.config_id for r in seen)

    # cancel before any dispatch: stream ends immediately, run() returns
    svc2 = ExploreService(transport.LoopbackPair(1).host(), poll_s=0.005)
    svc2.submit_sweep("x", Replay(knobs), "tpu_v4", "8", len(knobs))
    out = svc2.cancel("x")
    assert out == {"queued_dropped": 0, "inflight_orphaned": 0}
    assert svc2.status("x")["state"] == "cancelled"
    assert list(svc2.subscribe("x")) == []      # non-active: empty stream
    svc2.run()                                  # nothing active: returns


def test_checkpoint_root_namespaces_and_solo_resume():
    """Durable tenants under one ``checkpoint_root``: each gets its own
    sanitized namespace, and the snapshot+WAL a service writes is readable
    by a *solo* resume with no translation."""
    root = tempfile.mkdtemp(prefix="jexplore-tenant-")
    try:
        knobs_a = knob_list(21, 10)
        knobs_b = knob_list(22, 6)
        pair, threads = start_fleet(2)
        svc = ExploreService(pair.host(), timeout_s=60.0, poll_s=0.005,
                             batch_size=2, checkpoint_root=root,
                             checkpoint_every=4)
        sa = svc.submit_sweep("team/a", Replay(knobs_a), "tpu_v4", "8",
                              len(knobs_a))
        sb = svc.submit_sweep("b", Replay(knobs_b), "tpu_v4", "8",
                              len(knobs_b))
        svc.run()
        stop_fleet(pair, threads)
        assert sa.state == "done" and sb.state == "done"
        da = tenant_checkpoint_dir(root, "team/a")   # sanitized slug
        db = tenant_checkpoint_dir(root, "b")
        assert os.path.isdir(da) and os.path.isdir(db) and da != db
        assert any(f.startswith("snap_") for f in os.listdir(da))
        ck = ExplorationCheckpoint(da)
        evs = ck.read_events(0)
        assert sum(1 for e in evs if e["t"] == "tell") == len(knobs_a)
        info = restore_sweep(ck, Replay(knobs_a), ResultStore(),
                             ("time_s", "power_w"))
        assert info["outstanding"] == {}             # nothing was in flight
        assert info["next_id"] == len(knobs_a)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_control_verbs():
    svc = ExploreService(transport.LoopbackPair(1).host(), poll_s=0.005)
    knobs = knob_list(3, 4)
    # SUBMIT refused without a factory; STATUS/CANCEL always work
    r = svc.handle_control({"cmd": CONTROL_SUBMIT, "tenant": "t",
                            "spec": {"n": 4}})
    assert r["ok"] is False and "sweep_factory" in r["error"]
    svc.sweep_factory = lambda spec: dict(
        search=Replay(knobs), arch="tpu_v4", shape="8", n_samples=spec["n"])
    r = svc.handle_control({"cmd": CONTROL_SUBMIT, "tenant": "t",
                            "spec": {"n": 4}})
    assert r == {"ok": True, "tenant": "t"}
    r = svc.handle_control({"cmd": CONTROL_STATUS, "tenant": "t"})
    assert r["ok"] and r["status"]["state"] == "running"
    assert r["status"]["n_samples"] == 4
    r = svc.handle_control({"cmd": CONTROL_STATUS})
    assert r["ok"] and set(r["status"]) == {"t"}
    r = svc.handle_control({"cmd": CONTROL_CANCEL, "tenant": "t"})
    assert r["ok"]
    assert svc.status("t")["state"] == "cancelled"
    r = svc.handle_control({"cmd": CONTROL_CANCEL, "tenant": "nope"})
    assert r["ok"] is False and "KeyError" in r["error"]
    assert is_control_msg({"cmd": CONTROL_STATUS})
    assert not is_control_msg({"cmd": "batch"})


class ShadowAware(Replay):
    """A searcher with the shadow-aware pool hooks, recording what it gets."""

    def __init__(self, ks):
        super().__init__(ks)
        self.fp_fn = None
        self.residency = []

    def set_sw_fingerprint_fn(self, fn):
        self.fp_fn = fn

    def note_residency(self, fps):
        self.residency.append(set(fps))


def test_service_tenant_gets_the_shadow_aware_pool_hooks():
    """A tenant with a fingerprint_fn gets the searcher's fingerprint and
    residency hooks, as a solo sweep does; a tenant without one gets
    neither."""
    jc = JConfig(small_space(), n_chips=8)
    knobs = knob_list(8, 10)
    aware, plain = ShadowAware(knobs), ShadowAware(knob_list(9, 6))
    pair, threads = start_fleet(2)
    svc = ExploreService(pair.host(), timeout_s=60.0, poll_s=0.005,
                         batch_size=2)
    svc.submit_sweep("fp", aware, "tpu_v4", "8", len(knobs),
                     fingerprint_fn=jc.cache_key)
    svc.submit_sweep("nofp", plain, "tpu_v4", "8", 6)
    svc.run()
    stop_fleet(pair, threads)
    assert aware.fp_fn is not None
    assert all(aware.fp_fn(k) == jc.cache_key(TestConfig(-1, "tpu_v4", "8", k))
               for k in knobs)
    assert any(aware.residency)          # fleet residency reached the pool
    assert plain.fp_fn is None and plain.residency == []


def test_outbound_configs_carry_no_tenant_key():
    """The loop pushes each config as its four wire fields: the scheduler
    knows every config's tenant, so no frame carries a tag."""

    class Recording(transport.LoopbackHostTransport):
        def __init__(self, pair):
            super().__init__(pair)
            self.sent = []

        def push(self, client_id, msg):
            self.sent += transport.unframe_batch(msg)
            super().push(client_id, msg)

    knobs_a, knobs_b = knob_list(5, 9), knob_list(6, 7)
    pair, threads = start_fleet(2)
    host = Recording(pair)
    svc = ExploreService(host, timeout_s=60.0, poll_s=0.005, batch_size=3)
    svc.submit_sweep("a", Replay(knobs_a), "tpu_v4", "8", len(knobs_a))
    svc.submit_sweep("b", Replay(knobs_b), "tpu_v4", "8", len(knobs_b))
    svc.run()
    stop_fleet(pair, threads)
    assert len(host.sent) == len(knobs_a) + len(knobs_b)
    assert all(set(m) == {"config_id", "arch", "shape", "knobs"}
               for m in host.sent)


def test_wire_tenant_tag_tolerated_by_from_wire():
    """The sidecar key older services stamped on outbound configs must be
    invisible to TestConfig.from_wire."""
    wire = dict(tc(5).to_wire(), tenant="team-a")
    back = TestConfig.from_wire(wire)
    assert back.config_id == 5 and back.knobs == {"x": 5}
