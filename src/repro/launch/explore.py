"""The JExplore driver: JHost + search algorithm + a real model workload.

Reproduces the paper's experiments on the TPU adaptation:

    PYTHONPATH=src python -m repro.launch.explore \
        --workload llama2-7b --samples 200 --algorithm random \
        --clients 2 --out results/llama2_explore.csv

Each "board" is a tensor-parallel slice of ``--chips`` devices (default:
every device JAX sees, so a v5e host's four chips give tp=4); the workload
is the paper's generation task (prompt prefill + 150 greedy decode tokens).
Programs are compiled for the devices present and read, not run.
Hardware-ladder knobs (clock/HBM/ICI) re-evaluate the analytic JMeasure
model against the cached compiled artifact — exactly like re-clocking a
Jetson without redeploying the network; sw knobs recompile (JClient caches
by fingerprint).

``--shape train_4k`` etc. switch the workload to a training/prefill/decode
step of the assigned architectures on a dp×tp slice of the same devices.

GP surrogate modes and flags (bayesopt/pal only)
------------------------------------------------
``--gp incremental``  rank-append Cholesky per tell on the host CPU — O(n²)
  per update, cached across asks (the default, and the numerical reference).
``--gp refit``        full O(n³) refactor per ask (pre-incremental path,
  kept for benchmarking and equivalence tests).
``--gp jax``          device-resident fast path: the same incremental
  buffer layout lives on the accelerator as jitted, donated rank-appends;
  pool scoring (posterior means + EHVI staircase) is fused into one device
  call; past ``--gp-inducing`` observations a subset-of-data inducing-point
  approximation keeps the active set — and ask latency — flat into the
  10⁴+ regime.  Matches the numpy reference to float64 round-off while the
  active set is exact.
``--gp pallas``       the same device-resident layout with the two hot
  calls as tiled Pallas kernels: the rank-append Cholesky touches active-row
  tiles only (O(n·block) per tell instead of full-capacity GEMMs) and the
  fused predict+EHVI pool sweep stays in VMEM without materializing the
  (pool, n) cross-kernel matrix — the n = 10⁵–10⁶ tier.  Runs in interpret
  mode off-TPU, so it works (and is parity-tested) everywhere jax is.
``--gp-inducing N``   inducing-point threshold for ``--gp jax|pallas``
  (default 5000; the active set is thinned to a stride of the archive once
  observations exceed ~1.25×N).
``--gp-refresh K``    hyperparameter refresh schedule, any mode: every K
  tells the RBF lengthscale is re-tuned (isotropic *and* per-dimension ARD
  median-distance candidates scored by log marginal likelihood on a strided
  subsample) and the live factor is rebuilt in place.
``--speculate-slow-mult M``  queued-chunk speculation: chunks not yet
  started on a client whose per-config EWMA exceeds M× the median of the
  other healthy clients are mirrored elsewhere (first answer wins).
"""
import argparse
import os
import threading
import time


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="llama2-7b", help="arch id")
    p.add_argument("--shape", default="generate",
                   help="'generate' (paper workload) or a SHAPES name")
    p.add_argument("--samples", type=int, default=200)
    p.add_argument("--reduced", action="store_true",
                   help="shrink the arch (repro.configs.reduced) so smoke "
                        "and fault-injection tests compile in seconds")
    p.add_argument("--algorithm", default="random",
                   choices=["random", "grid", "nsga2", "bayesopt", "pal"])
    p.add_argument("--clients", type=int, default=2)
    p.add_argument("--chips", type=int, default=None,
                   help="chips per board (default: every device JAX sees)")
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen-tokens", type=int, default=150)
    p.add_argument("--out", default="results/explore.csv")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--batch-size", type=int, default=None,
                   help="configs per dispatched chunk (batched fast path); "
                        "default: scalar one-config-per-message dispatch")
    p.add_argument("--dispatch", default="eager",
                   choices=["eager", "pipelined"],
                   help="eager: a client gets its next chunk only after "
                        "answering its current one; pipelined: keep every "
                        "client's queue 2 chunks deep (double-buffering)")
    p.add_argument("--chunk-budget-ms", type=float, default=None,
                   help="adaptive chunk sizing: target this wall-time budget "
                        "per chunk from an EWMA of observed per-config wall "
                        "time per client (replaces the static --batch-size)")
    p.add_argument("--codec", default="json", choices=["json", "binary"],
                   help="wire codec: binary packs columnar frames' numeric "
                        "columns as typed arrays (fleet-friendly)")
    p.add_argument("--affinity", default="off",
                   choices=["off", "prefer", "strict"],
                   help="compile-affinity placement: route chunks to the "
                        "client already holding their sw fingerprint "
                        "compiled (prefer: steal rather than idle; strict: "
                        "a fingerprint's work always waits for its home "
                        "client while it is healthy)")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   help="queued chunks per client under --dispatch "
                        "pipelined (default 2 = double-buffering; deeper "
                        "hides higher-latency links)")
    p.add_argument("--speculate-at", type=float, default=None, metavar="FRAC",
                   help="speculative re-dispatch: mirror a running chunk to "
                        "a second client once it has burned this fraction "
                        "of its deadline budget (first answer wins)")
    p.add_argument("--speculate-slow-mult", type=float, default=None,
                   metavar="MULT",
                   help="queued-chunk speculation: mirror chunks not yet "
                        "started on a client whose per-config EWMA exceeds "
                        "this multiple of the median of the other healthy "
                        "clients' EWMAs (first answer wins)")
    p.add_argument("--cache-dir", default=None,
                   help="persistent artifact cache root: compiled artifacts "
                        "are pickled content-addressed under "
                        "<cache-dir>/client<i>/ so restarted clients and "
                        "repeated sweeps skip the compile (layout + "
                        "invalidation rules: repro.core.jclient docstring)")
    p.add_argument("--fleet-cache", default="off",
                   choices=["off", "serve", "relay"],
                   help="fleet-wide artifact store: clients missing both "
                        "local cache tiers fetch peers' compiled artifacts "
                        "through the host instead of recompiling (serve: "
                        "host keeps a blob cache; relay: host forwards "
                        "fetches to the resident peer), so N clients x F "
                        "fingerprints costs exactly F compiles")
    p.add_argument("--max-stale-tells", type=int, default=None,
                   help="with --async-search: discard precomputed asks "
                        "lagging the model by more than this many folded "
                        "tells (default: unbounded stale tolerance)")
    p.add_argument("--async-search", action="store_true",
                   help="precompute asks in a background worker and fold "
                        "tells in at ask boundaries (SearchDriver), so "
                        "model-based search math overlaps with client "
                        "evaluation instead of stalling the fleet")
    p.add_argument("--gp", default="incremental",
                   choices=["incremental", "refit", "jax", "pallas"],
                   help="bayesopt/pal surrogate update: incremental = "
                        "rank-append Cholesky per tell (O(n^2), cached "
                        "across asks); refit = full O(n^3) refactor per "
                        "ask (pre-PR behaviour, for benchmarking); jax = "
                        "device-resident jitted fast path with fused pool "
                        "scoring and inducing points; pallas = jax layout "
                        "with tiled active-row kernels for the append and "
                        "the fused EHVI sweep (see module docstring)")
    p.add_argument("--gp-inducing", type=int, default=5000,
                   help="--gp jax|pallas: inducing-point threshold — past "
                        "this many observations the active set is thinned "
                        "to a strided subset so ask latency stays flat")
    p.add_argument("--gp-refresh", type=int, default=None, metavar="K",
                   help="hyperparameter refresh: re-tune the GP lengthscale "
                        "every K tells, rebuilding the live factor in place "
                        "(any --gp mode; default: never)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="durable sweeps: snapshot searcher + scheduler state "
                        "here and write-ahead-log every ask/tell, so a "
                        "killed host can continue with --resume (zero "
                        "duplicate evaluations, bit-identical picks for "
                        "sync search)")
    p.add_argument("--checkpoint-every", type=int, default=25,
                   help="completed evaluations between snapshots (the WAL "
                        "covers the gap, so this only bounds replay length)")
    p.add_argument("--resume", action="store_true",
                   help="continue a sweep from --checkpoint-dir + --out: "
                        "re-adopts the CSV, replays the event log, and "
                        "re-submits formerly in-flight configs under their "
                        "original ids")
    p.add_argument("--chaos-crash-at", type=int, default=None, metavar="N",
                   help="fault injection: hard-kill the host (os._exit 42, "
                        "no flush/close — a kill -9 equivalent) the moment "
                        "the N-th result frame arrives; pair with --resume "
                        "to test crash recovery")
    p.add_argument("--chaos-drop", type=float, default=0.0, metavar="P",
                   help="fault injection: drop outbound config frames with "
                        "probability P (exercises deadline/retry/quarantine)")
    p.add_argument("--chaos-dup", type=float, default=0.0, metavar="P",
                   help="fault injection: duplicate outbound config frames "
                        "with probability P (exercises first-answer-wins)")
    p.add_argument("--chaos-seed", type=int, default=0,
                   help="RNG seed for the fault-injection wrapper")
    return with_default_chips(p.parse_args(argv))


def with_default_chips(args):
    """``--chips`` left unset means the devices this process actually has."""
    if args.chips is None:
        import jax

        args.chips = len(jax.devices())
    return args


def make_build_fn(args, jc):
    """Workload adapter: TestConfig -> (Artifact, meta).  Injected into
    JClient — 'the workloads can be anything' (paper §III).

    On a TPU the roofline model must hold the chip's peaks: an unknown
    ``device_kind`` is refused here, before any build."""
    import jax

    from repro.configs import SHAPES, get_arch, reduced
    from repro.core.tracing import span
    from repro.launch.build import build_cell, build_generation
    from repro.launch.mesh import make_mesh_dp_tp
    from repro.roofline.analysis import summarize
    from repro.roofline.hw import chip_peaks
    from repro.roofline.traffic import analytic_hbm_bytes_per_device

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        chip_peaks(dev.device_kind)

    def build(tc):
        arch = get_arch(tc.arch)
        if args.reduced:
            arch = reduced(arch)
        flags = jc.build_flags(tc.knobs)
        dp, tp = jc.mesh_factors(tc.knobs)
        mesh = make_mesh_dp_tp(dp, tp)
        if tc.shape == "generate":
            from repro.configs.base import ShapeConfig

            pre_cell, dec_cell = build_generation(
                arch, mesh, flags, batch=1,
                prompt_len=args.prompt_len,
                max_len=args.prompt_len + args.gen_tokens + 1)
            with span("jx.build.analyze") as sp:
                pre = summarize(pre_cell.compiled, mesh.size)
                dec = summarize(dec_cell.compiled, mesh.size)
                sp.set_metadata(**pre.count_stats("pre_"),
                                **dec.count_stats("dec_"))
                pre.hbm_est_per_device = analytic_hbm_bytes_per_device(
                    arch, ShapeConfig("p", "prefill", args.prompt_len, 1),
                    flags, mesh.size, dp, tp)
                dec.hbm_est_per_device = analytic_hbm_bytes_per_device(
                    arch, ShapeConfig("d", "decode",
                                      args.prompt_len + args.gen_tokens + 1,
                                      1),
                    flags, mesh.size, dp, tp)
            return pre, {"decode_artifact": dec,
                         "n_decode_tokens": args.gen_tokens}
        shape = SHAPES[tc.shape]
        cell = build_cell(arch, shape, mesh, flags)
        with span("jx.build.analyze") as sp:
            art = summarize(cell.compiled, mesh.size)
            sp.set_metadata(**art.count_stats(""))
            art.hbm_est_per_device = analytic_hbm_bytes_per_device(
                arch, shape, flags, mesh.size, dp, tp,
                optimizer=cell.meta.get("optimizer", "adamw"))
        return art, {}

    return build


def start_fleet(args, jc, build_fn):
    """Stand up the loopback fleet: one JClient serve-thread per board,
    each with its own persistent-cache subtree (like each board owning its
    own disk).  Shared by ``launch.explore`` (one sweep) and
    ``launch.serve_explore`` (multi-tenant service)."""
    from repro.core import JClient, transport

    pair = transport.LoopbackPair(args.clients, codec=args.codec)
    fleet_mode = None if args.fleet_cache == "off" else args.fleet_cache
    clients = [JClient(jc, build_fn, transport=pair.client(i), client_id=i,
                       cache_dir=(None if args.cache_dir is None else
                                  os.path.join(args.cache_dir, f"client{i}")),
                       fleet_mode=fleet_mode)
               for i in range(args.clients)]
    threads = [threading.Thread(target=c.serve,
                                kwargs=dict(poll_s=0.1, idle_limit_s=None),
                                daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    return pair, clients, fleet_mode


def generation_space(arch, chips):
    """Knob space for the paper's generation workload (batch=1 ⇒ dp=1)."""
    from repro.core.space import DesignSpace, Knob, KIND_HW, KIND_SW
    from repro.roofline import hw as hwmod

    knobs = [
        Knob("clock_scale", hwmod.CLOCK_LADDER, KIND_HW),
        Knob("hbm_scale", hwmod.HBM_LADDER, KIND_HW),
        Knob("ici_scale", hwmod.ICI_LADDER, KIND_HW),
        Knob("dp_degree", (1,), KIND_SW),
        Knob("dtype", ("bfloat16",), KIND_SW),
    ]
    if arch.n_heads:
        knobs += [Knob("attn_block_q", (128, 256, 512), KIND_SW),
                  Knob("attn_block_kv", (128, 256, 512), KIND_SW)]
    if arch.ssm_state:
        knobs += [Knob("ssd_chunk", (128, 256, 512), KIND_SW)]
    return DesignSpace(knobs)


def main(argv=None):
    """Run one sweep; returns its records and compile counters."""
    args = parse_args(argv)
    from repro.configs import get_arch, SHAPES
    from repro.core import (ALGORITHMS, JConfig, JHost, ResultStore,
                            tpu_pod_space, hypervolume)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    arch = get_arch(args.workload)
    if args.reduced:
        from repro.configs import reduced

        arch = reduced(arch)
    if args.shape == "generate":
        space = generation_space(arch, args.chips)
    else:
        space = tpu_pod_space(arch, SHAPES[args.shape], n_chips=args.chips)
    jc = JConfig(space, n_chips=args.chips)
    print(f"[explore] space size = {space.size()} "
          f"({len(space.knobs)} knobs); workload={args.workload}/{args.shape}")

    pair, clients, fleet_mode = start_fleet(args, jc, make_build_fn(args, jc))

    # pre-seed the CSV schema so a leading timeout/failure can't narrow it
    store = ResultStore(csv_path=args.out,
                        knob_names=[k.name for k in space],
                        metric_names=("time_s", "power_w"))
    host_transport = pair.host()
    chaos = None
    if (args.chaos_crash_at is not None or args.chaos_drop
            or args.chaos_dup):
        from repro.core import ChaosTransport

        chaos = ChaosTransport(host_transport, seed=args.chaos_seed,
                               drop_p=args.chaos_drop, dup_p=args.chaos_dup,
                               crash_at_results=args.chaos_crash_at)
        host_transport = chaos
    host = JHost(host_transport, store, timeout_s=args.timeout, poll_s=0.05)
    algo_kw = ({"gp_mode": args.gp,
                "hyper_refresh_every": args.gp_refresh,
                "inducing_threshold": args.gp_inducing}
               if args.algorithm in ("bayesopt", "pal") else {})
    fleet_store = None
    if fleet_mode is not None:
        from repro.core import FleetArtifactStore

        fleet_store = FleetArtifactStore(mode=fleet_mode)
    algo = ALGORITHMS[args.algorithm](space, seed=args.seed, **algo_kw)
    search = algo
    if args.async_search:
        from repro.core import SearchDriver

        search = SearchDriver(algo, mode="async",
                              max_stale_tells=args.max_stale_tells)
    t0 = time.time()
    try:
        host.explore(search, args.workload, args.shape, args.samples,
                     objectives=("time_s", "power_w"), progress=True,
                     batch_size=args.batch_size, dispatch=args.dispatch,
                     chunk_budget_ms=args.chunk_budget_ms,
                     affinity=args.affinity,
                     fingerprint_fn=(jc.cache_key if args.affinity != "off"
                                     or args.speculate_at is not None
                                     or args.speculate_slow_mult is not None
                                     or fleet_store is not None
                                     else None),
                     speculate_frac=args.speculate_at,
                     speculate_slow_mult=args.speculate_slow_mult,
                     pipeline_depth=args.pipeline_depth,
                     fleet_store=fleet_store,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every,
                     resume=args.resume)
    finally:
        if search is not algo:
            print(f"[explore] search driver: {search.stats()}")
            search.close()
    if chaos is not None:
        print(f"[explore] chaos: {chaos.stats()}")
    host.stop_clients()
    dt = time.time() - t0

    ok = store.ok_records()
    pts = store.objective_matrix(["time_s", "power_w"])
    front = store.pareto_front(["time_s", "power_w"])
    ref = pts.max(0) * 1.1
    compiles = sum(c.n_compiled for c in clients)
    build_s = [s for c in clients for s in c.build_seconds]
    print(f"[explore] {len(ok)} configs in {dt:.1f}s "
          f"({len(ok) / max(dt, 1e-9):.1f} evals/s; {compiles} compiles "
          f"in {sum(build_s):.1f}s, {len(ok)-compiles} cache hits)")
    if args.cache_dir is not None or fleet_store is not None:
        from repro.launch.report import cache_effectiveness

        line, _ = cache_effectiveness(
            [c.cache_info() for c in clients],
            fleet_store.stats() if fleet_store is not None else None)
        print(f"[explore] {line}")
    print(f"[explore] pareto front size = {len(front)}, "
          f"hypervolume = {hypervolume(pts, ref):.4g}")
    print(f"[explore] time range  [{pts[:,0].min():.3f}, {pts[:,0].max():.3f}] s")
    print(f"[explore] power range [{pts[:,1].min():.1f}, {pts[:,1].max():.1f}] W")
    print(f"[explore] results -> {args.out}")
    return {"records": list(store.records), "wall_s": dt,
            "build_seconds": [c.build_seconds for c in clients]}


if __name__ == "__main__":
    main()
