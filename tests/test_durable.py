"""Durable, elastic sweeps: exploration checkpoint/resume, dynamic fleet
membership, and deterministic fault injection.

The acceptance bar (ISSUE: durability tentpole): a host killed mid-sweep
and restarted with resume finishes with **zero duplicate evaluations** and
a **bit-identical** result sequence vs an uninterrupted run; a board can
join mid-run (HELLO) and receive dispatches while another leaves
(GOODBYE drain) without losing a config.  Snapshot→restore must reproduce
bit-identical BayesOpt/PAL pick sequences across every ``gp_mode``.
"""
import os
import pickle
import threading
import time

import numpy as np
import pytest

from repro.core import (ALGORITHMS, BayesOpt, ChaosTransport,
                        DispatchScheduler, ExplorationCheckpoint, JClient,
                        JConfig, JHost, ResultStore, SearchDriver,
                        TestConfig, transport)
from repro.core.durable import restore_sweep
from repro.core.results import ResultRecord
from repro.core.space import DesignSpace, KIND_HW, KIND_SW, Knob


def _space():
    return DesignSpace([
        Knob("clock_scale", (0.5, 0.75, 1.0), KIND_HW),
        Knob("hbm_scale", (0.25, 0.5, 1.0), KIND_HW),
        Knob("blk", (64, 128, 256, 512), KIND_SW),
        Knob("dtype", ("bfloat16", "float32"), KIND_SW),
    ])


def _objective(space, knobs):
    x = space.encode(knobs)
    return np.array([1.0 + float(x.sum()),
                     2.0 - float(x[0]) + 0.3 * float(x[1])])


# ---------------------------------------------------------------------------
# snapshot → restore reproduces bit-identical pick sequences
# ---------------------------------------------------------------------------

GP_MODES = ("incremental", "refit", "jax", "pallas")


@pytest.mark.parametrize("algo", ["bayesopt", "pal"])
@pytest.mark.parametrize("gp_mode", GP_MODES)
@pytest.mark.parametrize("seed", [0, 7])
def test_snapshot_restore_bit_identical_picks(algo, gp_mode, seed):
    """Poor-man's property test: at an arbitrary snapshot point, a fresh
    instance restored from state_dict must continue with exactly the picks
    the original would have made — for both model-based searchers, every
    surrogate mode, and multiple seeds."""
    if gp_mode in ("jax", "pallas"):
        pytest.importorskip("jax")
    space = _space()

    def mk():
        return ALGORITHMS[algo](space, seed=seed, n_init=6, pool_size=48,
                                gp_mode=gp_mode)

    a = mk()
    for _ in range(3):                       # past n_init, GP live
        for c in a.ask(4):
            a.tell(c, _objective(space, c))
    # pickle round-trip: snapshots are pickled to disk, and the copy keeps
    # the restored instance from sharing mutable state with the original
    state = pickle.loads(pickle.dumps(a.state_dict()))
    b = mk()
    b.load_state(state)
    for _ in range(4):
        pa, pb = a.ask(4), b.ask(4)
        assert pa == pb
        for c in pa:
            y = _objective(space, c)
            a.tell(c, y)
            b.tell(c, y)


def test_driver_sync_state_roundtrip():
    space = _space()

    def mk():
        return SearchDriver(BayesOpt(space, seed=1, n_init=6, pool_size=48,
                                     gp_mode="incremental"), mode="sync")

    a = mk()
    for _ in range(3):
        for c in a.ask(4):
            a.tell(c, _objective(space, c))
    state = pickle.loads(pickle.dumps(a.state_dict()))
    b = mk()
    b.load_state(state)
    for _ in range(3):
        pa, pb = a.ask(4), b.ask(4)
        assert pa == pb
        for c in pa:
            y = _objective(space, c)
            a.tell(c, y)
            b.tell(c, y)


def test_driver_async_state_roundtrip_no_duplicates():
    """Async resume is *correct*, not bit-identical: the restored driver
    must never re-issue a config the original already picked."""
    space = _space()
    a = SearchDriver(BayesOpt(space, seed=2, n_init=6, pool_size=48,
                              gp_mode="incremental"), mode="async")
    try:
        seen = []
        for _ in range(3):
            got = []
            while len(got) < 4:
                got.extend(a.ask(4 - len(got)))
            for c in got:
                a.tell(c, _objective(space, c))
            seen.extend(map(repr, got))
        state = pickle.loads(pickle.dumps(a.state_dict()))
    finally:
        a.close()
    b = SearchDriver(BayesOpt(space, seed=2, n_init=6, pool_size=48,
                              gp_mode="incremental"), mode="async")
    try:
        b.load_state(state)
        got = []
        while len(got) < 8:
            got.extend(b.ask(8 - len(got)))
        assert not set(map(repr, got)) & set(seen)
    finally:
        b.close()


# ---------------------------------------------------------------------------
# ExplorationCheckpoint mechanics
# ---------------------------------------------------------------------------

def test_checkpoint_save_latest_and_gc(tmp_path):
    ck = ExplorationCheckpoint(str(tmp_path), keep=2)
    for i in range(5):
        ck.save({"i": i}, block=True)
    ck.close()
    seqs = ck.all_seqs()
    assert len(seqs) == 2                    # keep-k GC
    snap = ExplorationCheckpoint(str(tmp_path)).latest()
    assert snap["i"] == 4 and seqs[-1] == 5


def test_wal_roundtrip_and_positions(tmp_path):
    ck = ExplorationCheckpoint(str(tmp_path))
    ck.open_log()
    ck.log_ask([0, 1], [{"x": 1}, {"x": 2}])
    pos = ck.log_pos()
    ck.log_tell(0, {"x": 1}, np.array([1.5, 2.5]))
    ck.close()
    evs = ExplorationCheckpoint(str(tmp_path)).read_events()
    assert [e["t"] for e in evs] == ["ask", "tell"]
    assert evs[1]["y"] == [1.5, 2.5]
    # suffix read from a recorded position skips the prefix
    suffix = ExplorationCheckpoint(str(tmp_path)).read_events(from_pos=pos)
    assert [e["t"] for e in suffix] == ["tell"]


def test_wal_torn_tail_is_repaired(tmp_path):
    ck = ExplorationCheckpoint(str(tmp_path))
    ck.open_log()
    ck.log_ask([0], [{"x": 1}])
    ck.log_tell(0, {"x": 1}, np.array([1.0]))
    ck.close()
    path = os.path.join(str(tmp_path), ExplorationCheckpoint.EVENTS)
    with open(path, "a") as f:
        f.write('{"t": "tell", "id": 1, "kno')   # killed mid-write
    ck2 = ExplorationCheckpoint(str(tmp_path))
    assert [e["t"] for e in ck2.read_events()] == ["ask", "tell"]
    size = ck2.open_log()                    # truncates the torn tail
    ck2.close()
    assert os.path.getsize(path) == size
    with open(path) as f:
        assert len(f.read().splitlines()) == 2


class _SpySearch:
    def __init__(self):
        self.asked, self.told = [], []

    def ask(self, n):
        self.asked.append(n)
        return [{"x": i} for i in range(n)]

    def tell(self, knobs, y):
        self.told.append((knobs, np.asarray(y)))


def test_restore_sweep_late_tell_reconciliation(tmp_path):
    """A row the CSV flushed but whose tell never reached the WAL (the
    crash window between store.add and log_tell) is told on restore, in
    CSV order, and never re-submitted."""
    ck = ExplorationCheckpoint(str(tmp_path))
    ck.open_log()
    ck.log_ask([0, 1], [{"x": 0}, {"x": 1}])
    ck.log_tell(0, {"x": 0}, np.array([1.0, 2.0]))
    ck.close()                               # id 1 completed but not logged
    store = ResultStore()
    for cid in (0, 1):
        store.add(ResultRecord(config_id=cid, arch="a", shape="s",
                               knobs={"x": cid},
                               metrics={"time_s": 1.0 + cid,
                                        "power_w": 2.0 + cid}))
    search = _SpySearch()
    info = restore_sweep(ExplorationCheckpoint(str(tmp_path)), search, store,
                         ("time_s", "power_w"))
    assert not info["from_snapshot"]
    assert info["n_late_tells"] == 1
    assert info["outstanding"] == {}         # both completed: nothing re-runs
    assert info["next_id"] == 2
    # replayed tell for 0, late tell for 1 — exactly one each
    assert [k["x"] for k, _ in search.told] == [0, 1]
    np.testing.assert_array_equal(search.told[1][1], [2.0, 3.0])


# ---------------------------------------------------------------------------
# torn CSV row (satellite)
# ---------------------------------------------------------------------------

def test_torn_trailing_csv_row_is_skipped_and_dropped(tmp_path):
    p = str(tmp_path / "r.csv")
    store = ResultStore(csv_path=p, knob_names=("x",),
                        metric_names=("time_s",))
    store.add(ResultRecord(config_id=0, arch="a", shape="s", knobs={"x": 1},
                           metrics={"time_s": 1.0}))
    store.close()
    with open(p, "a") as f:
        f.write("1,a,s,ok,0,False")          # killed mid-writerow
    fresh = ResultStore(csv_path=p, knob_names=("x",),
                        metric_names=("time_s",))
    assert fresh.resume_from_csv() == 1      # torn row skipped, not fatal
    assert fresh.records[0].config_id == 0
    assert fresh.records[0].metrics["time_s"] == 1.0
    fresh.add(ResultRecord(config_id=1, arch="a", shape="s", knobs={"x": 2},
                           metrics={"time_s": 2.0}))
    fresh.close()
    with open(p) as f:
        lines = f.read().splitlines()
    assert len(lines) == 3                   # header + 2 rows: torn row gone


# ---------------------------------------------------------------------------
# dynamic fleet membership (scheduler-level, fake clock, no threads)
# ---------------------------------------------------------------------------

def _clk():
    class FakeClock:
        t = 0.0

        def __call__(self):
            return self.t
    return FakeClock()


def _tc(i):
    return TestConfig(i, "a", "s", {"x": i})


def _ok(cid, client):
    return {"config_id": cid, "status": "ok", "client_id": client,
            "metrics": {"time_s": 1.0}, "cached": False, "wall_s": 0.0}


def test_add_client_mid_run_receives_dispatches():
    s = DispatchScheduler([0], policy="eager", batch_size=2, clock=_clk())
    for i in range(6):
        s.submit(_tc(i))
    d = s.next_dispatches()
    assert [c for c, _ in d] == [0]
    slot = s.add_client(1)
    assert not slot.quarantined and s.stats()["clients"] == 2
    d2 = s.next_dispatches()                 # joiner picks up pending work
    assert [c for c, _ in d2] == [1]
    assert s.stats()["clients_joined"] == 1


def test_remove_client_drains_without_losing_configs():
    s = DispatchScheduler([0, 1], policy="eager", batch_size=2, clock=_clk())
    for i in range(4):
        s.submit(_tc(i))
    d = dict(s.next_dispatches())
    s.remove_client(1, drain=True)           # graceful leave: finish first
    assert s.slots[1].draining
    assert s.want() == 0                     # no new demand for the leaver
    for cfg in d[1]:
        s.on_result(_ok(cfg.config_id, 1))   # drained answers still count
    assert 1 not in s.slots                  # retired once empty
    assert s.stats()["clients_left"] == 1
    # remaining work flows to the survivor; nothing was lost
    for cfg in d[0]:
        s.on_result(_ok(cfg.config_id, 0))
    assert all(c == 0 for c, _ in s.next_dispatches())


def test_remove_client_hard_requeues_without_consuming_retries():
    s = DispatchScheduler([0, 1], policy="eager", batch_size=2,
                          max_retries=0, clock=_clk())
    for i in range(2):
        s.submit(_tc(i))
    d = dict(s.next_dispatches())
    assert list(d) == [0]
    s.remove_client(0, drain=False)          # hard leave (powered off)
    assert 0 not in s.slots
    # with max_retries=0 an *expiry* would be terminal; a hard leave is not
    # a config failure, so the work is requeued with full budget
    d2 = dict(s.next_dispatches())
    assert sorted(c.config_id for c in d2[1]) == [0, 1]


def test_rejoin_after_leave_and_shadow_warm():
    s = DispatchScheduler([0], policy="eager", batch_size=1, clock=_clk(),
                          affinity="prefer",
                          fingerprint_fn=lambda tc: ("fp", tc.knobs["x"] % 2))
    s.submit(_tc(0))
    d = dict(s.next_dispatches())
    s.on_result(_ok(0, 0))
    fp_repr = repr(("fp", 0))
    slot = s.add_client(1, resident_fps=[fp_repr])
    assert ("fp", 0) in slot.shadow          # repr string mapped back
    s.remove_client(1, drain=False)
    slot2 = s.add_client(1)                  # rejoin gets a fresh slot
    assert not slot2.draining and 1 in s.slots


# ---------------------------------------------------------------------------
# ChaosTransport
# ---------------------------------------------------------------------------

def test_chaos_drop_and_dup_are_seeded_and_counted():
    pair = transport.LoopbackPair(1)
    drop = ChaosTransport(pair.host(), seed=3, drop_p=1.0)
    drop.push(0, {"cmd": "x"})
    assert drop.stats()["dropped"] == 1
    assert pair.client(0).pull(0.0) is None
    dup = ChaosTransport(pair.host(), seed=3, dup_p=1.0)
    dup.push(0, {"cmd": "y"})
    assert dup.stats()["duplicated"] == 1
    ct = pair.client(0)
    assert ct.pull(0.0)["cmd"] == "y" and ct.pull(0.0)["cmd"] == "y"


def test_chaos_killed_client_goes_dark_both_ways():
    pair = transport.LoopbackPair(2)
    ch = ChaosTransport(pair.host(), seed=0)
    ch.kill_client(0)
    ch.push(0, {"cmd": "x"})                 # swallowed
    assert pair.client(0).pull(0.0) is None
    pair.client(0).push({"config_id": 1, "client_id": 0, "status": "ok"})
    pair.client(1).push({"config_id": 2, "client_id": 1, "status": "ok"})
    got = ch.pull_many(0.1) + ch.pull_many(0.1)   # drain both frames
    assert [m["client_id"] for m in got] == [1]
    assert 0 not in ch.client_ids()
    assert ch.stats()["killed_frames"] == 2


def test_chaos_crash_fires_once_before_delivery():
    pair = transport.LoopbackPair(1)
    fired = []
    ch = ChaosTransport(pair.host(), crash_at_results=1,
                        crash_fn=lambda: fired.append(1))
    pair.client(0).push({"config_id": 0, "client_id": 0, "status": "ok"})
    assert ch.pull_many(0.1) == [{"config_id": 0, "client_id": 0,
                                  "status": "ok"}]
    pair.client(0).push({"config_id": 1, "client_id": 0, "status": "ok"})
    assert ch.pull_many(0.1) == []           # frame lost with the crash
    assert fired == [1]
    pair.client(0).push({"config_id": 2, "client_id": 0, "status": "ok"})
    assert len(ch.pull_many(0.1)) == 1       # fired once, not again


# ---------------------------------------------------------------------------
# end-to-end: crash mid-sweep, resume, bit-identical result
# ---------------------------------------------------------------------------

class _Boom(Exception):
    pass


def _toy_workload():
    from repro.roofline.analysis import Artifact

    space = _space()
    jc = JConfig(space, n_chips=8)

    def build(tc):
        import zlib

        h = zlib.crc32(repr(jc.cache_key(tc)).encode()) % 7 + 1
        art = Artifact(flops_per_device=5e12 * h, bytes_per_device=2e10,
                       wire_bytes_per_device=1e8, collectives={},
                       arg_bytes=10 ** 9, temp_bytes=10 ** 8,
                       output_bytes=10 ** 6, n_devices=8)
        return art, {}

    return space, jc, build


def _explore_leg(tmp_path, tag, *, n=36, crash_at=None, resume=False,
                 checkpoint_every=12):
    space, jc, build = _toy_workload()
    pair = transport.LoopbackPair(1)
    cl = JClient(jc, build, transport=pair.client(0), client_id=0)
    threading.Thread(target=cl.serve, kwargs=dict(poll_s=0.005),
                     daemon=True).start()
    ht = pair.host()
    if crash_at is not None:
        def _boom():
            raise _Boom()
        ht = ChaosTransport(ht, crash_at_results=crash_at, crash_fn=_boom)
    store = ResultStore(csv_path=str(tmp_path / tag / "r.csv"),
                        knob_names=[k.name for k in space],
                        metric_names=("time_s", "power_w"))
    host = JHost(ht, store, timeout_s=60.0, poll_s=0.002)
    search = BayesOpt(space, seed=4, n_init=8, pool_size=64,
                      gp_mode="incremental")
    crashed = False
    try:
        host.explore(search, "toy", "gen", n, batch_size=6,
                     dispatch="eager",
                     checkpoint_dir=str(tmp_path / tag / "ckpt"),
                     checkpoint_every=checkpoint_every, resume=resume)
    except _Boom:
        crashed = True
    host.stop_clients()
    store.close()
    return store, crashed


def _sweep_key(store):
    return {r.config_id: (tuple(sorted(r.knobs.items())),
                          float(r.metrics["time_s"]).hex(),
                          float(r.metrics["power_w"]).hex())
            for r in store.records}


@pytest.mark.parametrize("crash_at", [7, 20])
def test_crash_resume_zero_dups_bit_identical(tmp_path, crash_at):
    """crash_at=7 dies before any snapshot (full WAL replay against a
    fresh searcher); crash_at=20 dies past one (snapshot + suffix)."""
    straight, _ = _explore_leg(tmp_path, "u", n=36)
    _, crashed = _explore_leg(tmp_path, "c", n=36, crash_at=crash_at)
    assert crashed
    resumed, crashed2 = _explore_leg(tmp_path, "c", n=36, resume=True)
    assert not crashed2
    ids = [r.config_id for r in resumed.records]
    assert len(ids) == len(set(ids)) == 36   # zero duplicate evaluations
    assert set(ids) == set(range(36))
    assert _sweep_key(resumed) == _sweep_key(straight)   # bit-identical


def test_elastic_join_and_drain_end_to_end(tmp_path):
    """A client HELLOs in mid-sweep and serves dispatches; another
    GOODBYEs (drain) and leaves without losing a config."""
    space, jc, build = _toy_workload()

    def slow_build(tc):
        time.sleep(0.002)
        return build(tc)

    pair = transport.LoopbackPair(2)
    clients = {i: JClient(jc, slow_build, transport=pair.client(i),
                          client_id=i) for i in range(2)}
    for c in clients.values():
        threading.Thread(target=c.serve, kwargs=dict(poll_s=0.005),
                         daemon=True).start()
    store = ResultStore()
    host = JHost(pair.host(), store, timeout_s=60.0, poll_s=0.002)
    from repro.core import RandomSearch

    search = RandomSearch(space, seed=0)
    err = []

    def run():
        try:
            host.explore(search, "toy", "gen", 60, batch_size=5,
                         dispatch="pipelined")
        except Exception as e:
            err.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.time() + 60.0
    while len(store.records) < 10 and t.is_alive() and time.time() < deadline:
        time.sleep(0.002)
    pair.add_client(2)                       # mailbox before serve (loopback)
    clients[2] = JClient(jc, slow_build, transport=pair.client(2),
                         client_id=2)
    threading.Thread(target=clients[2].serve, kwargs=dict(poll_s=0.005),
                     daemon=True).start()
    clients[2].hello()
    while len(store.records) < 25 and t.is_alive() and time.time() < deadline:
        time.sleep(0.002)
    clients[1].goodbye(drain=True)
    t.join(60.0)
    host.stop_clients()
    assert not err, err
    ids = [r.config_id for r in store.records]
    assert len(ids) == len(set(ids)) == 60
    assert all(r.status == "ok" for r in store.records)
    served = {r.client_id for r in store.records}
    assert 2 in served                       # the joiner did real work
    stats = host.scheduler.stats()
    assert stats["clients_joined"] >= 1 and stats["clients_left"] >= 1


# ---------------------------------------------------------------------------
# a solo sweep on a scripted board: frozen output, ids across a crash
# ---------------------------------------------------------------------------

class _Board(transport.HostTransport):
    """One board answered in-process and in order: every config pushed
    comes back on the next pull as a slim result whose metrics are
    ``_objective`` of its knobs.  The pull numbered ``crash_at`` raises
    (the host dies with its last dispatch in flight); the frames in
    ``late`` ride the pull numbered ``late_at``."""

    def __init__(self, crash_at=None, late=(), late_at=None):
        self.space = _space()
        self.crash_at, self.late, self.late_at = crash_at, list(late), late_at
        self.pushes = []       # every message pushed, as pushed
        self.due = []          # configs pushed and not yet answered
        self.n_pulls = 0

    def client_ids(self):
        return [0]

    def push(self, client_id, msg):
        self.pushes.append(msg)
        self.due += [m for m in transport.unframe_batch(msg)
                     if "config_id" in m]

    def pull_many(self, timeout_s):
        self.n_pulls += 1
        if self.n_pulls == self.crash_at:
            raise _Boom()
        out = [self.answer(m) for m in self.due]
        self.due = []
        if self.n_pulls == self.late_at:
            out = self.late + out
        return out

    def answer(self, cfg):
        t, p = _objective(self.space, cfg["knobs"])
        return {"config_id": cfg["config_id"], "client_id": 0,
                "status": "ok",
                "metrics": {"time_s": float(t), "power_w": float(p)}}


def _board_leg(tmp_path, board, *, n=30, batch_size=4, dispatch="eager",
               resume=False):
    from repro.core import RandomSearch

    class Told(RandomSearch):
        def tell(self, knobs, y):
            self.told.append((knobs, [float(v).hex() for v in y]))
            super().tell(knobs, y)

    search = Told(board.space, seed=3)
    search.told = []
    store = ResultStore(csv_path=str(tmp_path / "r.csv"),
                        knob_names=[k.name for k in board.space],
                        metric_names=("time_s", "power_w"))
    try:
        JHost(board, store, timeout_s=60.0, poll_s=0.0).explore(
            search, "toy", "gen", n, batch_size=batch_size,
            dispatch=dispatch, checkpoint_dir=str(tmp_path / "ckpt"),
            checkpoint_every=8, resume=resume)
    finally:
        store.close()
    return store, search


def _sha(obj):
    import hashlib
    import json

    if not isinstance(obj, bytes):
        obj = json.dumps(obj, sort_keys=True).encode()
    return hashlib.sha256(obj).hexdigest()[:16]


def _leg_digests(tmp_path, store, board, search):
    with open(tmp_path / "r.csv", "rb") as f:
        csv_bytes = f.read()
    with open(tmp_path / "ckpt" / ExplorationCheckpoint.EVENTS, "rb") as f:
        wal = f.read()
    snap = ExplorationCheckpoint(str(tmp_path / "ckpt")).latest()
    return {
        "wire": _sha(board.pushes),
        "records": _sha([dict(r.to_wire(), metrics={
            k: float(v).hex() for k, v in r.metrics.items()})
            for r in store.records]),
        "told": _sha(search.told),
        "csv": _sha(csv_bytes), "wal": _sha(wal),
        "snapshot": _sha({k: snap[k] for k in ("event_pos", "next_id",
                                               "outstanding", "meta")}),
    }


# what the solo loop put on the wire and on disk before it ran as a
# one-tenant service, for the straight sweep and its crash + resume
_FROZEN_SOLO = {
    (None, "eager"): {
        "resume_line": "eb8c16cf1258ace8",
        "straight": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "d7643b7bb1413cd9", "told": "5eb496e30c6923f2",
            "wal": "c65fdcace7dec025", "wire": "614066eb04c4b97f"},
        "resumed": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "9618172e2bfcc689", "told": "5eb496e30c6923f2",
            "wal": "c65fdcace7dec025", "wire": "6486c93afad3261d"},
    },
    (4, "eager"): {
        "resume_line": "a7c7c618897141da",
        "straight": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "505870ced71367b9", "told": "5eb496e30c6923f2",
            "wal": "72ed1a8d846116f2", "wire": "4bffee09514ccb09"},
        "resumed": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "505870ced71367b9", "told": "5eb496e30c6923f2",
            "wal": "72ed1a8d846116f2", "wire": "4a2b29d740c46021"},
    },
    (4, "pipelined"): {
        "resume_line": "bbcbff84e7b1d800",
        "straight": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "60fff77b53e91098", "told": "5eb496e30c6923f2",
            "wal": "cc154b8c5db00c9b", "wire": "4bffee09514ccb09"},
        "resumed": {
            "csv": "bfd83e5a855ac109", "records": "754f4713c563ac63",
            "snapshot": "60fff77b53e91098", "told": "5eb496e30c6923f2",
            "wal": "cc154b8c5db00c9b", "wire": "4e7350d5cd7f0647"},
    },
}


@pytest.mark.parametrize("batch_size,dispatch", [
    (None, "eager"), (4, "eager"), (4, "pipelined")])
def test_solo_sweep_output_is_frozen(tmp_path, capsys, batch_size, dispatch):
    """A fixed solo sweep, straight and crashed + resumed: its wire
    frames, records, tells, CSV, WAL and last snapshot hash to the values
    the earlier solo loop produced."""
    got = {}
    board = _Board()
    store, search = _board_leg(tmp_path / "u", board, batch_size=batch_size,
                               dispatch=dispatch)
    got["straight"] = _leg_digests(tmp_path / "u", store, board, search)
    with pytest.raises(_Boom):
        _board_leg(tmp_path / "c", _Board(crash_at=3),
                   batch_size=batch_size, dispatch=dispatch)
    capsys.readouterr()
    board = _Board()
    store, search = _board_leg(tmp_path / "c", board, batch_size=batch_size,
                               dispatch=dispatch, resume=True)
    got["resumed"] = _leg_digests(tmp_path / "c", store, board, search)
    got["resume_line"] = _sha([ln for ln in capsys.readouterr().out
                               .splitlines() if ln.startswith("# resume")])
    assert got == _FROZEN_SOLO[(batch_size, dispatch)]


@pytest.mark.parametrize("late_at", [1, 2, 3, 4])
def test_late_answer_from_before_a_crash_keeps_its_config(tmp_path,
                                                          late_at):
    """A board that outlived the host answers, after the resume, the chunk
    it held at the crash under that chunk's wire ids: each answer either
    completes the config it was for or is dropped as a duplicate, never
    completes another config."""
    crashed = _Board(crash_at=3)
    with pytest.raises(_Boom):
        _board_leg(tmp_path, crashed)
    assert crashed.due                       # a chunk was in flight
    late = [crashed.answer(m) for m in crashed.due]
    board = _Board(late=late, late_at=late_at)
    store, _ = _board_leg(tmp_path, board, resume=True)
    ids = sorted(r.config_id for r in store.records)
    assert ids == list(range(30))
    for r in store.records:
        t, p = _objective(board.space, r.knobs)
        assert (r.metrics["time_s"], r.metrics["power_w"]) == (t, p), \
            r.config_id


def test_a_cancelled_solo_sweep_raises(tmp_path):
    """A wire CANCEL naming the solo sweep ends it early: ``explore``
    raises rather than return a partial store."""
    from repro.core.transport import CONTROL_CANCEL

    board = _Board(late=[{"cmd": CONTROL_CANCEL, "tenant": "default"}],
                   late_at=2)
    with pytest.raises(RuntimeError, match="cancelled at"):
        _board_leg(tmp_path, board)
