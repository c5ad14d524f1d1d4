"""One run of one cell: set-up, a measured window of sweeps, the checks.

The window drives ``repro.core.JHost.explore``, composed from
``repro.launch.explore``'s own pieces exactly as its ``main()`` does
(``parse_args``, ``generation_space``, ``make_build_fn``, ``start_fleet``).
The harness adds two thin recorders and patches nothing:

* ``SearchRecorder`` is handed to ``explore`` as the search.  It times every
  ``ask`` and ``tell`` (host spans, and trace annotations in a traced run),
  stamps each proposed config so its ask-to-tell latency can be read, and
  closes the window: the first ``ask`` after the deadline ends the sweep.
  It also records the surrogate's ``fit_y``/``predict`` calls on the
  searcher's GP instance, the answers the search check compares.
* ``BuildRecorder`` wraps the ``build_fn`` handed to each ``JClient``: it
  times each build and keeps the artifact's counts for the measure and build
  checks.

Sweeps run back to back, closed loop, each on a fresh fleet; sweep ``i``
uses search seed ``seed * 1000 + i``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import counts, reference
from bench.spec import Spec

# config-file model keys -> the program's ArchConfig fields they must equal
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "vocab_size": "vocab_size", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "sliding_window": "sliding_window",
    "state_size": "ssm_state", "ssm_state_size": "ssm_state",
    "mamba_head_dim": "ssm_head_dim", "expand": "ssm_expand",
    "conv_kernel": "ssm_conv",
    "n_routed_experts": "n_experts", "num_experts": "n_experts",
    "router_dtype": "router_dtype",
    "num_experts_per_tok": "moe_top_k", "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "tie_word_embeddings": "tie_embeddings",
}
# what the program runs where its ArchConfig has no field of that name
PROGRAM_IMPLIED = {
    "router_dtype": lambda arch: "float32",
    "ffn_act": lambda arch: "silu",
}
# every other key a configuration file's model may state; a key outside
# these and ARCH_FIELDS is counted by no reference and held by no check
MODEL_KEYS = {"kind", "layers", "head_dim", "pad_vocab_size_multiple",
              "hidden_act", "mlp_hidden_act"}
# the configuration file's mixer names -> the program's LayerSpec mixers
MIXER_NAMES = {"attention": "attn", "attention_window": "attn_local",
               "mamba2": "mamba"}
WARMUP_SWEEP = 999          # search seed offset of the set-up sweep
# a traced run profiles the first seconds of its window only: the device
# tracer's buffer fills in about 20 s of the warm cell and drops what follows
TRACE_SECONDS = 15.0


class WindowClosed(Exception):
    """Raised from ``ask`` once the window's deadline has passed."""


class NoChip(RuntimeError):
    pass


def pow2_small(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def knob_key(knobs: dict) -> tuple:
    return tuple(sorted((k, repr(v)) for k, v in knobs.items()))


def annotate(name: str, on: bool):
    """A host span in the profiler's trace, in a traced run only."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class TraceWindow:
    """The profiled part of a traced window: its first ``seconds``, marked
    by a ``bench.window`` span on the profiler's own clock."""

    def __init__(self, trace_dir: str, seconds: float):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.t0 = time.monotonic()
        self.until = self.t0 + seconds
        self.t1: Optional[float] = None

    def maybe_stop(self, now: float) -> None:
        if self.t1 is None and now >= self.until:
            self.stop()

    def stop(self) -> None:
        if self.t1 is None:
            import jax

            self.t1 = time.monotonic()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class Sweep:
    index: int
    t_start: float
    t_end: Optional[float] = None          # None: closed by the window
    error: Optional[str] = None
    told: List[dict] = dataclasses.field(default_factory=list)
    tells: List[Tuple[float, np.ndarray]] = dataclasses.field(
        default_factory=list)
    n_compiled: int = 0
    records: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class GPCall:
    sweep: int
    n_obs: int
    y: np.ndarray
    xq: np.ndarray
    mu: np.ndarray
    sig: np.ndarray


class Recorder:
    """Everything one window records; the per-layer readers read it."""

    def __init__(self, deadline: float, traced: bool):
        self.deadline = deadline
        self.traced = traced
        self.t0 = self.t1 = 0.0          # window open and close, monotonic
        self.trace: Optional[TraceWindow] = None
        self.spans: List[Tuple[str, float, float]] = []
        self.latencies: List[Tuple[float, float]] = []  # (tell, seconds)
        self.sweeps: List[Sweep] = []
        self.builds: List[Tuple[float, float]] = []
        self.gp_calls: List[GPCall] = []
        self.gp_flops: List[Tuple[float, int]] = []    # (time, operations)


class SearchRecorder:
    """The search ``explore`` drives: the searcher, timed and recorded."""

    def __init__(self, inner, rec: Recorder, sweep: Sweep,
                 sample_calls: set):
        self.inner = inner
        self.rec = rec
        self.sweep = sweep
        self.asked: Dict[tuple, List[float]] = {}
        self.last_call: Optional[GPCall] = None
        self._wrap_surrogate(sample_calls)

    def ask(self, n):
        t0 = time.monotonic()
        if self.rec.trace is not None:
            self.rec.trace.maybe_stop(t0)
        if t0 >= self.rec.deadline:
            raise WindowClosed
        with annotate("bench.ask", self.rec.traced):
            out = self.inner.ask(n)
        t1 = time.monotonic()
        self.rec.spans.append(("ask", t0, t1))
        for knobs in out:
            self.asked.setdefault(knob_key(knobs), []).append(t0)
        return out

    def tell(self, knobs, y):
        t0 = time.monotonic()
        with annotate("bench.tell", self.rec.traced):
            self.inner.tell(knobs, y)
        t1 = time.monotonic()
        self.rec.spans.append(("tell", t0, t1))
        self.sweep.told.append(dict(knobs))
        self.sweep.tells.append((t0, np.array(y, float)))
        starts = self.asked.get(knob_key(knobs))
        if starts:
            self.rec.latencies.append((t0, t0 - starts.pop(0)))

    def _wrap_surrogate(self, sample_calls: set):
        """Record the answers of the searcher's GP where they are produced:
        its ``fit_y`` targets and its ``predict`` posteriors, on the
        instance, for the calls whose index is in ``sample_calls`` and for
        the latest call of the sweep."""
        gp = getattr(self.inner, "_gp", None)
        if gp is None or not hasattr(gp, "predict"):
            return
        rec, sweep = self.rec, self.sweep
        fit_y, predict, observe = gp.fit_y, gp.predict, gp.observe
        state = {"y": None, "i": 0}

        def cap():
            return int(getattr(gp, "_cap", 0) or pow2_small(max(len(gp), 16)))

        def observe_rec(x_new):
            x_new = np.atleast_2d(np.asarray(x_new, float))
            out = observe(x_new)
            rec.gp_flops.append((time.monotonic(), counts.append_flops(
                cap(), pow2_small(len(x_new)), x_new.shape[1])))
            return out

        def fit_y_rec(y):
            state["y"] = y
            rec.gp_flops.append((time.monotonic(), counts.fit_y_flops(cap())))
            return fit_y(y)

        def reuses():
            stats = getattr(gp, "stats", None)
            return stats().get("predict_reuses", 0) if stats else 0

        def predict_rec(xs):
            before = reuses()
            mu, sig = predict(xs)
            xs = np.atleast_2d(xs)
            # a reuse runs the mean alone, on the last full predict's pool
            count = (counts.predict_mean_flops if reuses() > before
                     else counts.predict_flops)
            rec.gp_flops.append((time.monotonic(), count(
                cap(), pow2_small(len(xs)), xs.shape[1])))
            call = GPCall(sweep.index, len(state["y"]), state["y"], xs,
                          mu, sig)
            if state["i"] in sample_calls:
                rec.gp_calls.append(call)
            self.last_call = call
            state["i"] += 1
            return mu, sig

        gp.observe, gp.fit_y, gp.predict = observe_rec, fit_y_rec, predict_rec


class BuildRecorder:
    """The ``build_fn`` each ``JClient`` gets: the program's, timed, with the
    artifact's counts kept by software fingerprint."""

    def __init__(self, inner, sw_names: List[str]):
        self.inner = inner
        self.sw_names = sw_names
        self.rec: Optional[Recorder] = None     # the window being recorded
        self.artifacts: Dict[tuple, dict] = {}
        self.all_builds: List[Tuple[tuple, dict]] = []

    def fingerprint(self, knobs: dict) -> tuple:
        return tuple((n, repr(knobs[n])) for n in self.sw_names)

    def __call__(self, tc):
        rec = self.rec
        t0 = time.monotonic()
        with annotate("bench.build", rec is not None and rec.traced):
            art, meta = self.inner(tc)
        if rec is not None:
            rec.builds.append((t0, time.monotonic()))
        fp = self.fingerprint(tc.knobs)
        got = {"pre": artifact_counts(art),
               "dec": (artifact_counts(meta["decode_artifact"])
                       if "decode_artifact" in meta else None)}
        self.artifacts.setdefault(fp, got)
        self.all_builds.append((fp, got))
        return art, meta


def artifact_counts(art) -> dict:
    hbm = getattr(art, "hbm_est_per_device", None)
    return {"flops_per_device": float(art.flops_per_device),
            "hbm_bytes_per_device": float(art.bytes_per_device
                                          if hbm is None else hbm),
            "wire_bytes_per_device": float(art.wire_bytes_per_device),
            "n_devices": int(art.n_devices),
            "arg_bytes": int(art.arg_bytes),
            "temp_bytes": int(art.temp_bytes),
            "output_bytes": int(art.output_bytes)}


def arch_fields(model: dict) -> Dict[str, object]:
    """The program's ``ArchConfig`` fields a configuration file's model sizes
    set, by ``ARCH_FIELDS``; the vocabulary as the rows the model holds, and
    the feed-forward activation where the file states one."""
    fields = dict(ARCH_FIELDS, head_dim=("ssm_head_dim"
                                         if model["kind"] == "mamba2"
                                         else "head_dim"))
    out = {field: model[key] for key, field in fields.items() if key in model}
    if "vocab_size" in model:
        out["vocab_size"] = reference.vocab_rows(model)
    if "mlp_hidden_act" in model or "hidden_act" in model:
        out["ffn_act"] = reference.ffn_act(model)
    return out


def program_value(arch, field: str):
    """An ``ArchConfig`` field or property, or what the program implies
    where it has neither."""
    if hasattr(arch, field):
        return getattr(arch, field)
    return PROGRAM_IMPLIED[field](arch)


def size_mismatches(model: dict, arch) -> List[str]:
    """Where the program's architecture departs from the configuration
    file's sizes and layer list; empty where it runs what the file states."""
    out = [f"the configuration file states {field}={value!r}, the program "
           f"runs {field}={program_value(arch, field)!r}"
           for field, value in arch_fields(model).items()
           if program_value(arch, field) != value]
    unknown = sorted(set(model) - set(ARCH_FIELDS) - MODEL_KEYS)
    if unknown:
        out.append(f"the configuration file states {', '.join(unknown)}, "
                   f"which the reference does not count")
    want = [(MIXER_NAMES.get(m, m), f)
            for m, f in reference.layer_kinds(model)]
    got = [(s.mixer, s.ffn) for s in arch.layer_specs()]
    for i, (w, g) in enumerate(zip(want, got)):
        if w != g:
            out.append(f"the configuration file states layer {i} as "
                       f"{w}, the program runs {g}")
            break
    return out


def workload_arch(cfg: dict) -> str:
    """The workload name the program explores for a configuration file.

    The program's registered architecture where it holds the file's sizes;
    otherwise (a cut in depth, a tied head, a padded vocabulary) those sizes
    registered through the program's own ``register`` as a workload named
    after the configuration, the way each of its config modules adds one.
    Sizes that are no field of ``ArchConfig`` are left to ``check_sizes``."""
    from repro.configs import get_arch
    from repro.configs.base import register

    base = get_arch(cfg["arch"])
    fields = arch_fields(cfg["model"])
    if all(program_value(base, f) == v for f, v in fields.items()):
        return base.name
    settable = {f.name for f in dataclasses.fields(base)} & set(fields)
    fields = {f: fields[f] for f in settable}
    name = cfg["name"] + ".configured"
    try:
        arch = get_arch(name)
    except KeyError:
        arch = register(dataclasses.replace(base, name=name, **fields))
    if any(getattr(arch, f) != v for f, v in fields.items()):
        raise ValueError(f"workload {name!r} is registered with other sizes")
    return name


class Cell:
    """A cell's program objects, built from its configuration and traffic."""

    def __init__(self, spec: Spec, name: str, cache_root: str):
        from repro.configs import get_arch, reduced
        from repro.core import JConfig
        from repro.launch import explore

        self.name = name
        self.entry = spec.cell(name)
        self.cfg = spec.config(self.entry["config"])
        self.traffic = spec.traffic(self.entry["traffic"])
        t, w = self.traffic, self.cfg["workload"]
        self.chips = int(self.cfg["chips_per_board"])
        workload = workload_arch(self.cfg)
        argv = ["--workload", workload, "--shape", "generate",
                "--chips", str(self.chips), "--clients", str(t["boards"]),
                "--samples", str(t["samples"]),
                "--algorithm", t["algorithm"],
                "--gp", t.get("gp", "incremental"),
                "--batch-size", str(t["batch_size"]),
                "--dispatch", t["dispatch"],
                "--prompt-len", str(w["prompt_len"]),
                "--gen-tokens", str(w["gen_tokens"]),
                "--timeout", str(t["timeout_s"])]
        if self.cfg.get("program_reduced"):
            argv.append("--reduced")
        self.cache_dir = None
        if t["artifact_cache"] == "warm":
            self.cache_dir = os.path.join(cache_root, "artifacts")
            argv += ["--cache-dir", self.cache_dir]
        self.args = explore.parse_args(argv)
        arch = get_arch(workload)
        if self.cfg.get("program_reduced"):
            arch = reduced(arch)
        self.arch = arch
        self.check_sizes()
        self.space = explore.generation_space(arch, self.chips)
        self.jc = JConfig(self.space, n_chips=self.chips)
        self.space_values = [(k.name, tuple(k.values)) for k in self.space]
        self.builds = BuildRecorder(
            explore.make_build_fn(self.args, self.jc),
            sorted(k.name for k in self.space if k.kind == "sw"))

    def check_sizes(self):
        """The configuration file holds the sizes the program runs."""
        bad = size_mismatches(self.cfg["model"], self.arch)
        if bad:
            raise ValueError(f"{self.cfg['name']}: " + "; ".join(bad))

    def all_configs(self) -> List[dict]:
        names = [n for n, _ in self.space_values]
        return [dict(zip(names, vals)) for vals in
                itertools.product(*(v for _, v in self.space_values))]


def reference_front(cell: Cell) -> dict:
    """Every configuration of the space through the program's own build and
    measure (one ``JClient``, no transport); in a warm cell its client
    writes the artifact cache the window's boards read."""
    from repro.core import JClient
    from repro.core.jconfig import TestConfig

    client = JClient(cell.jc, cell.builds,
                     cache_dir=(None if cell.cache_dir is None else
                                os.path.join(cell.cache_dir, "client0")))
    tcs = [TestConfig(i, cell.args.workload, "generate", k)
           for i, k in enumerate(cell.all_configs())]
    res = client.evaluate_batch(tcs)
    bad = [r for r in res if r["status"] != "ok"]
    if bad:
        raise RuntimeError(f"{len(bad)} of {len(res)} configurations failed "
                           f"in set-up: {bad[0]['metrics'].get('error')}")
    pts = np.asarray([[r["metrics"]["time_s"], r["metrics"]["power_w"]]
                      for r in res], float)
    ref_pt = pts.max(0) * 1.1
    return {"ref_point": ref_pt,
            "hv": reference.hypervolume_2d(pts, ref_pt),
            "n": len(pts)}


def run_sweep(cell: Cell, rec: Recorder, index: int, seed: int,
              sample_calls: set) -> Sweep:
    """One ``launch.explore`` invocation's worth of work on a fresh fleet."""
    from repro.core import ALGORITHMS, JHost, ResultStore
    from repro.launch import explore

    args = cell.args
    sweep = Sweep(index=index, t_start=time.monotonic())
    rec.sweeps.append(sweep)
    before = set(threading.enumerate())
    pair, clients, _ = explore.start_fleet(args, cell.jc, cell.builds)
    threads = [t for t in threading.enumerate() if t not in before]
    store = ResultStore(knob_names=[k.name for k in cell.space],
                        metric_names=("time_s", "power_w"))
    host = JHost(pair.host(), store, timeout_s=args.timeout, poll_s=0.05)
    algo_kw = ({"gp_mode": args.gp, "hyper_refresh_every": args.gp_refresh,
                "inducing_threshold": args.gp_inducing}
               if args.algorithm in ("bayesopt", "pal") else {})
    algo = ALGORITHMS[args.algorithm](cell.space, seed=seed, **algo_kw)
    search = SearchRecorder(algo, rec, sweep, sample_calls)
    try:
        host.explore(search, args.workload, "generate", args.samples,
                     objectives=("time_s", "power_w"),
                     batch_size=args.batch_size, dispatch=args.dispatch)
        sweep.t_end = time.monotonic()
    except WindowClosed:
        pass
    except Exception as e:          # the program failed: the run is not
        sweep.error = repr(e)       # correct, and the window ends here
        log(f"sweep {index} failed:\n{traceback.format_exc()}")
    finally:
        host.stop_clients()
        for t in threads:
            t.join(timeout=300)
    sweep.n_compiled = sum(c.n_compiled for c in clients)
    sweep.records = list(store.records)
    if search.last_call is not None:
        rec.gp_calls.append(search.last_call)
    return sweep


def sampled_calls(seed: int, index: int, expected: int, k: int) -> set:
    """Indices of the GP calls a sweep records, drawn from the seed."""
    rng = np.random.default_rng([seed % (1 << 63), index])
    return set(rng.choice(max(expected, 1), size=min(k, max(expected, 1)),
                          replace=False).tolist())


def run_window(cell: Cell, seed: int, seconds: float,
               trace_dir: Optional[str] = None) -> Recorder:
    """Sweeps back to back for ``seconds``; with ``trace_dir``, the first
    ``TRACE_SECONDS`` of them are profiled into it."""
    expected = cell.traffic["samples"]
    k = int(cell.traffic.get("check", {}).get("gp_calls_per_sweep", 0))
    start = time.monotonic()
    rec = Recorder(deadline=start + seconds, traced=trace_dir is not None)
    if trace_dir is not None:
        rec.trace = TraceWindow(trace_dir, min(seconds, TRACE_SECONDS))
    cell.builds.rec = rec
    rec.t0 = start
    i = 0
    while time.monotonic() < rec.deadline:
        sweep = run_sweep(cell, rec, i, seed * 1000 + i,
                          sampled_calls(seed, i, expected, k))
        if sweep.error is not None:
            break
        i += 1
    rec.t1 = time.monotonic()
    if rec.trace is not None:
        rec.trace.stop()
    cell.builds.rec = None
    return rec


def hv_time(sweep: Sweep, front: dict, deadline: float,
            share: float = 0.95) -> Optional[float]:
    """Seconds from the sweep's start until the hypervolume of its told
    results reaches ``share`` of the reference; a sweep that ended inside
    the window without reaching it counts its whole duration; one still
    running at the close without reaching it counts nothing."""
    target = share * front["hv"]
    pts = np.zeros((0, 2))
    for t, y in sweep.tells:
        if t > deadline:
            break
        if len(pts) and np.any(np.all(pts <= y, axis=1)):
            continue                       # dominated or equal: hv unchanged
        pts = np.vstack([pts, y[None, :]])
        pts = pts[reference.nondominated(pts)]
        if reference.hypervolume_2d(pts, front["ref_point"]) >= target:
            return t - sweep.t_start
    if sweep.t_end is not None and sweep.t_end <= deadline:
        return sweep.t_end - sweep.t_start
    return None


def finished_sweeps(rec: Recorder) -> List[Sweep]:
    """The sweeps that ended inside the window."""
    return [s for s in rec.sweeps
            if s.t_end is not None and s.t_end <= rec.deadline]


def end_to_end(rec: Recorder, front: dict, seconds: float) -> Dict[str, float]:
    """The window's end-to-end numbers: ``ok`` evaluations told per second;
    ``sweep_s``, the window's wall seconds from its start to the end of
    its last finished sweep over the number of finished sweeps, so a
    fleet's start and teardown between sweeps count as well; ``hv95_s``,
    the mean of ``hv_time`` over the sweeps; and the 95th percentile of
    ask-to-tell latency."""
    done = [t for s in rec.sweeps for t, _ in s.tells if t <= rec.deadline]
    lat = [d for t, d in rec.latencies if t <= rec.deadline]
    hv = [h for h in (hv_time(s, front, rec.deadline) for s in rec.sweeps)
          if h is not None]
    swept = finished_sweeps(rec)
    out = {"evals_per_s": len(done) / seconds}
    if swept:
        out["sweep_s"] = (max(s.t_end for s in swept) - rec.t0) / len(swept)
    if hv:
        out["hv95_s"] = float(np.mean(hv))
    if lat:
        out["eval_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
    return out


def log_sweeps(rec: Recorder, front: dict) -> None:
    """Each sweep of the window: its seconds, its time to 95% of the
    reference hypervolume, and the seconds of each build it ran; then the
    median seconds of the finished sweeps, a diagnostic beside ``sweep_s``
    that leaves out the time between sweeps."""
    for s in rec.sweeps:
        end = rec.t1 if s.t_end is None else s.t_end
        builds = [round(e - b, 3) for b, e in rec.builds
                  if s.t_start <= b < end]
        hv = hv_time(s, front, rec.deadline)
        log(f"sweep {s.index}: {end - s.t_start:.3f}s"
            f"{'' if s.t_end else ' (closed)'}, hv95 "
            f"{'-' if hv is None else f'{hv:.3f}s'}, {len(s.tells)} told, "
            f"builds {builds}")
    swept = finished_sweeps(rec)
    if swept:
        med = float(np.median([s.t_end - s.t_start for s in swept]))
        log(f"median finished sweep {med!r}s over {len(swept)}")


def memory_peak_bytes(n: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def devices(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"JAX finds no accelerator (platform "
                         f"{dev.platform!r}): this run measures nothing")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return info


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def set_up(spec: Spec, workload: str, seed: int, cache_root: str):
    """Build the cell, measure the whole space for the reference front and
    run one sweep that warms every program the window's sweeps use."""
    t0 = time.monotonic()
    cell = Cell(spec, workload, cache_root)
    t1 = time.monotonic()
    front = reference_front(cell)
    t2 = time.monotonic()
    warm = Recorder(deadline=math.inf, traced=False)
    cell.builds.rec = warm
    run_sweep(cell, warm, WARMUP_SWEEP, seed * 1000 + WARMUP_SWEEP, set())
    cell.builds.rec = None
    built = sum(e - s for s, e in warm.builds)
    log(f"set-up phases: cell {t1 - t0:.3f}s, reference front "
        f"{t2 - t1:.3f}s, warm-up sweep {time.monotonic() - t2:.3f}s "
        f"({len(warm.builds)} builds, {built:.3f}s)")
    return cell, front, warm


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, require_chip: bool = True,
        peaks_kind: Optional[str] = None) -> dict:
    """One whole run; returns the result line's fields."""
    from bench import check, metrics_io

    spec = Spec(root)
    chips = int(spec.config(spec.cell(workload)["config"])["chips_per_board"])
    dev = devices(chips, require_chip)
    log(f"devices found {time.monotonic() - t_process:.3f}s after the "
        f"process start")
    peaks = reference.load_peaks(peaks_kind or dev["kind"])
    cache_root = tempfile.mkdtemp(prefix="bench-")
    try:
        cell, front, warm = set_up(spec, workload, seed, cache_root)
        setup_s = time.monotonic() - t_process
        log(f"set-up {setup_s:.3f}s: {front['n']} configurations, reference "
            f"hypervolume {front['hv']:.6g}; set-up sweep "
            f"{len(warm.sweeps[0].tells)} evaluations")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        rec = run_window(cell, seed, seconds, trace_dir)
        mem = memory_peak_bytes(chips)
        e2e = end_to_end(rec, front, seconds)
        e2e["setup_s"] = setup_s
        log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
        log(f"window {rec.t1 - rec.t0:.3f}s: {len(rec.sweeps)} sweeps, "
            f"{sum(len(s.tells) for s in rec.sweeps)} evaluations told, "
            f"{len(rec.builds)} builds")
        log_sweeps(rec, front)
        verdict = check.run_checks(cell, rec, peaks)
        out = {"correct": verdict["correct"],
               "attempted": verdict["attempted"],
               "failed": verdict["failed"],
               "device": dict(dev, memory_peak_bytes=mem)}
        if trace:
            layer = metrics_io.per_layer(spec, cell, rec, peaks, trace_dir)
            out["metrics"] = layer["metrics"]
            out["device"].update(layer.get("device_trace", {}))
            if "breakdown" in layer:
                out["breakdown"] = layer["breakdown"]
        else:
            out["metrics"] = metrics_io.end_to_end(spec, workload, e2e)
        out["checks"] = verdict["checks"]
        return out
    finally:
        shutil.rmtree(cache_root, ignore_errors=True)
