#!/usr/bin/env python3
"""Bring-up smoke run on a TPU: the exploration loop and one generation,
driven through the launchers' own ``main()`` entry points, all in this one
process (a chip belongs to one process at a time).

    python3 chip_smoke.py               # one chip: phases A and B
    python3 chip_smoke.py --four-chips  # four chips: phases C and D

Phase A  ``launch.explore``: BayesOpt with the device GP (``--gp jax``) over
         llama2-7b's generation workload at full width (6.7B bf16 params,
         prompt 64, 150 decode tokens), compiled for one chip by two boards.
         24 samples, so the GP answers asks after its 12 random picks.  Every
         config must come back ``ok`` with a finite ``time_s``, and the
         device GP's posterior must match the float64 numpy reference.
Phase B  ``launch.serve``: tinyllama-1.1b at full width greedy-generates 32
         tokens for 4 prompts of 64; every token must lie in [0, vocab).
Phase C  ``launch.explore`` at ``--chips 4`` (tp=4 on the real mesh) beside
         the one-chip build of the same config, and a tinyllama-1.1b prefill
         cell on a (1, 4) mesh against a (1, 1) mesh with the same weights:
         the sharded bf16 logits must be as close to a float32 one-chip
         reference as the one-chip bf16 logits are.
Phase D  Yi-9B on the four chips, tensor-parallel 4 ways: the 48-layer
         build of the exploration loop (``make_build_fn``) must count the
         per-device wire bytes and FLOPs of prefill and decode within 5% of
         the plain count (``tests/plain_llama.py``); and at published
         widths with 2 layers, the float32 prefill, then decode through the
         cache, run on the chips must match the plain float32 forward on one
         chip within 1e-4 of the largest |logit|.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failure,
and a run that finds no TPU, exits non-zero without that line.
"""
import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULTS = os.path.join(HERE, "results")

# device GP posterior vs the float64 numpy reference, relative to max |mean|
GP_RTOL = 1e-6
# tp=4 bf16 prefill logits may stray from the float32 one-chip reference
# by at most this multiple of what the one-chip bf16 run strays
BF16_ERR_RATIO = 2.0

EXPLORE_1CHIP = ["--workload", "llama2-7b", "--shape", "generate",
                 "--chips", "1", "--clients", "2", "--samples", "24",
                 "--algorithm", "bayesopt", "--gp", "jax", "--batch-size", "4",
                 "--seed", "0"]
SERVE = ["--arch", "tinyllama-1.1b", "--dtype", "bfloat16", "--batch", "4",
         "--prompt-len", "64", "--gen", "32"]
EXPLORE_4CHIP = ["--workload", "llama2-7b", "--shape", "generate",
                 "--chips", "4", "--clients", "1", "--samples", "4",
                 "--algorithm", "random", "--batch-size", "4", "--seed", "0"]
TP_PREFILL = ("tinyllama-1.1b", 4, 256)          # arch, batch, prompt
# phase D: the exploration loop's Yi-9B generation workload on four chips
YI_EXPLORE = ["--workload", "yi-9b", "--shape", "generate", "--chips", "4",
              "--clients", "1", "--prompt-len", "64", "--gen-tokens", "150"]
COUNT_RTOL = 0.05           # per-device wire bytes and FLOPs vs plain count
LOGIT_RTOL = 1e-4           # float32 tp=4 logits vs the plain forward
YI_DECODE_STEPS = 4


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def run_sweep(argv, out_name):
    """``launch.explore.main`` on ``argv``; every config must be ok."""
    from repro.launch import explore

    argv = argv + ["--out", os.path.join(RESULTS, out_name)]
    want = explore.parse_args(argv).samples
    t0 = time.perf_counter()
    out = explore.main(argv)
    wall = time.perf_counter() - t0
    recs = out["records"]
    bad = [r for r in recs if r.status != "ok"
           or not math.isfinite(r.metrics.get("time_s", math.nan))]
    if bad:
        log(f"first error (config {bad[0].config_id}): "
            f"{bad[0].metrics.get('error', bad[0].status)}")
    check(len(recs) == want and not bad,
          f"{len(recs) - len(bad)}/{want} configs ok")
    builds = out["build_seconds"]
    cold = [b[0] for b in builds if b]
    warm = [s for b in builds for s in b[1:]]
    log(f"sweep: {len(recs)}/{want} configs ok in {wall:.1f}s; "
        f"{len(cold) + len(warm)} compiles in {sum(cold) + sum(warm):.1f}s; "
        f"cold {_mean(cold):.2f}s/build ({len(cold)}), "
        f"warm {_mean(warm):.2f}s/build ({len(warm)})")
    return out, wall


def _mean(xs):
    return sum(xs) / len(xs) if xs else float("nan")


def check_device_gp(n=24, d=7, n_query=64, seed=0):
    """JaxIncrementalGP on the chip against the numpy IncrementalGP."""
    import numpy as np

    from repro.core.search.bayesopt import IncrementalGP
    from repro.core.search.gp_jax import JaxIncrementalGP

    rng = np.random.default_rng(seed)
    x, xq = rng.random((n, d)), rng.random((n_query, d))
    y = np.sin(3 * x).sum(1) + 0.1 * rng.standard_normal(n)
    mu_ref, sig_ref = IncrementalGP().observe(x).fit_y(y).predict(xq)
    mu, sig = JaxIncrementalGP().observe(x).fit_y(y).predict(xq)
    err = max(np.abs(mu - mu_ref).max(), np.abs(sig - sig_ref).max())
    rel = err / np.abs(mu_ref).max()
    log(f"device GP vs numpy float64: max rel diff {rel:.3e} "
        f"(tolerance {GP_RTOL:g})")
    check(rel <= GP_RTOL, f"device GP differs from numpy by {rel:.3e}")


def phase_a():
    run_sweep(EXPLORE_1CHIP, "chip_smoke_explore.csv")
    check_device_gp()


def phase_b():
    import numpy as np

    from repro.launch import serve

    args = serve.parse_args(SERVE)
    t0 = time.perf_counter()
    arch, res = serve.main(SERVE)
    wall = time.perf_counter() - t0
    toks = np.asarray(res.tokens)
    check(toks.shape == (args.batch, args.gen),
          f"tokens shape {toks.shape}, want {(args.batch, args.gen)}")
    check(bool(((toks >= 0) & (toks < arch.vocab_size)).all()),
          f"tokens outside [0, {arch.vocab_size})")
    log(f"serve: {toks.size} tokens in [0, {arch.vocab_size}) in {wall:.1f}s "
        f"(compile included)")


def _collectives(compiled, n_devices):
    """What the roofline parser finds, beside raw counts in the HLO text."""
    from repro.roofline.analysis import collective_wire_bytes

    txt = compiled.as_text()
    raw = {k: txt.count(f" {k}(") + txt.count(f" {k}-start(")
           for k in ("all-reduce", "reduce-scatter", "all-gather",
                     "all-to-all", "collective-permute")}
    return {"parsed_wire_bytes": collective_wire_bytes(txt, n_devices),
            "hlo_op_counts": raw, "kCustom_fusions": txt.count("kind=kCustom"),
            "while_loops": txt.count(" while(")}


def phase_c():
    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.core.jconfig import JConfig, TestConfig
    from repro.launch import explore
    from repro.launch.build import build_cell
    from repro.launch.mesh import make_mesh_dp_tp
    from repro.models import BuildFlags, Model

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, want 4")
    out, _ = run_sweep(EXPLORE_4CHIP, "chip_smoke_explore_4chip.csv")
    tc = TestConfig(0, "llama2-7b", "generate", out["records"][0].knobs)
    arts = {}
    for chips in (4, 1):
        argv = list(EXPLORE_4CHIP)
        argv[argv.index("--chips") + 1] = str(chips)
        args = explore.parse_args(argv)
        jc = JConfig(explore.generation_space(get_arch(tc.arch), chips),
                     n_chips=chips)
        pre, meta = explore.make_build_fn(args, jc)(tc)
        arts[chips] = (pre, meta["decode_artifact"])
    for i, kind in enumerate(("prefill", "decode")):
        a4, a1 = arts[4][i], arts[1][i]
        log(f"llama2-7b {kind} per device, tp=4 vs tp=1: args "
            f"{a4.arg_bytes} vs {a1.arg_bytes} B "
            f"({a4.arg_bytes / a1.arg_bytes:.3f}x), flops "
            f"{a4.flops_per_device:.6g} vs {a1.flops_per_device:.6g} "
            f"({a4.flops_per_device / a1.flops_per_device:.3f}x); "
            f"tp=4 collectives {a4.collectives}")
        check(a4.arg_bytes < 0.5 * a1.arg_bytes,
              f"tp=4 {kind} holds {a4.arg_bytes} B per device")

    name, batch, seq = TP_PREFILL
    arch = get_arch(name)
    params = Model(arch, BuildFlags()).init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                arch.vocab_size, jax.numpy.int32)
    shape = ShapeConfig("tp_prefill", "prefill", seq, batch)
    logits = {}
    for tp, dtype in ((4, "bfloat16"), (1, "bfloat16"), (1, "float32")):
        cell = build_cell(arch, shape, make_mesh_dp_tp(1, tp),
                          BuildFlags(dtype=dtype))
        p_sh, b_sh = cell.compiled.input_shardings[0]
        cast = jax.tree.map(lambda a: a.astype(dtype), params)
        lg, _ = cell.compiled(jax.device_put(cast, p_sh),
                              jax.device_put({"tokens": tokens}, b_sh))
        logits[tp, dtype] = np.asarray(jax.device_get(lg), np.float32)
        check(np.isfinite(logits[tp, dtype]).all(),
              f"tp={tp} {dtype} logits not finite")
        if tp == 4:
            log(f"{name} prefill tp=4 collectives: "
                f"{json.dumps(_collectives(cell.compiled, tp))}")
    ref = logits[1, "float32"]

    def err(lg):
        return float(np.abs(lg - ref).max() / np.abs(ref).max())

    l4, l1 = logits[4, "bfloat16"], logits[1, "bfloat16"]
    e4, e1 = err(l4), err(l1)
    agree = float((l4.argmax(-1) == l1.argmax(-1)).mean())
    log(f"{name} prefill logits vs float32 (1,1) mesh, max rel diff: bf16 "
        f"(1,4) {e4:.3e}, bf16 (1,1) {e1:.3e} (allowed ratio "
        f"{BF16_ERR_RATIO:g}); (1,4) vs (1,1) "
        f"{float(np.abs(l4 - l1).max() / np.abs(l1).max()):.3e}; "
        f"argmax agreement {agree:.3f}")
    check(e4 <= BF16_ERR_RATIO * e1,
          f"tp=4 bf16 logits stray {e4:.3e} from float32, one chip {e1:.3e}")


def phase_d():
    import dataclasses

    import jax
    import numpy as np

    from plain_llama import forward, tp_step_counts
    from repro.configs import get_arch
    from repro.core.jconfig import JConfig, TestConfig
    from repro.launch import explore
    from repro.launch.build import build_generation
    from repro.launch.mesh import make_mesh_dp_tp
    from repro.models import BuildFlags, Model

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, want 4")
    args = explore.parse_args(YI_EXPLORE)
    arch = get_arch("yi-9b")
    space = explore.generation_space(arch, args.chips)
    knobs = {k.name: k.values[-1] for k in space}
    pre, meta = explore.make_build_fn(args, JConfig(space, n_chips=4))(
        TestConfig(0, arch.name, "generate", knobs))
    sizes = dict(layers=arch.n_layers, d_model=arch.d_model,
                 n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
                 head_dim=arch.d_head, d_ff=arch.d_ff, vocab=arch.vocab_size,
                 tp=4, act_bytes=2)
    max_len = args.prompt_len + args.gen_tokens + 1
    for art, tokens, context in (
            (pre, args.prompt_len, args.prompt_len),
            (meta["decode_artifact"], 1, max_len)):
        kind = "prefill" if tokens > 1 else "decode"
        wire, flops = tp_step_counts(tokens=tokens, context=context, **sizes)
        log(f"yi-9b tp=4 {kind} per device: wire {art.wire_bytes_per_device:.6g}"
            f" B (plain {wire:.6g}, {art.wire_bytes_per_device / wire:.4f}x), "
            f"flops {art.flops_per_device:.6g} (plain {flops:.6g}, "
            f"{art.flops_per_device / flops:.4f}x); collectives "
            f"{art.collectives}; loop trips {art.loop_trips}")
        for got, want, what in ((art.wire_bytes_per_device, wire, "wire"),
                                (art.flops_per_device, flops, "flops")):
            check(abs(got / want - 1) <= COUNT_RTOL,
                  f"yi-9b tp=4 {kind} {what} {got:.6g} against {want:.6g}")

    small = dataclasses.replace(arch, n_layers=2, name="yi-9b-2l")
    prompt = args.prompt_len
    max_len = prompt + YI_DECODE_STEPS + 1
    params = Model(small, BuildFlags(dtype="float32")).init(jax.random.key(0))
    tokens = np.asarray(jax.random.randint(
        jax.random.key(1), (prompt + YI_DECODE_STEPS,), 0, small.vocab_size),
        np.int32)
    with jax.default_matmul_precision("highest"):
        pre_c, dec_c = build_generation(small, make_mesh_dp_tp(1, 4),
                                        BuildFlags(dtype="float32"), batch=1,
                                        prompt_len=prompt, max_len=max_len)
    p_sh, b_sh = pre_c.compiled.input_shardings[0]
    logits, caches = pre_c.compiled(
        jax.device_put(params, p_sh),
        jax.device_put({"tokens": tokens[None, :prompt]}, b_sh))
    rows = [np.asarray(logits)[0]]
    d_sh = dec_c.compiled.input_shardings[0]
    caches = jax.device_put(jax.tree.map(
        lambda c: np.pad(np.asarray(c), [(0, 0)] * (c.ndim - 3)
                         + [(0, max_len - prompt), (0, 0), (0, 0)]),
        caches), d_sh[2])
    params_d = jax.device_put(params, d_sh[0])
    for j in range(YI_DECODE_STEPS):
        logits, caches = dec_c.compiled(
            params_d, jax.device_put(tokens[None, prompt + j:prompt + j + 1],
                                     d_sh[1]),
            caches, jax.device_put(np.int32(prompt + j), d_sh[3]))
        rows.append(np.asarray(logits)[0])
    one_chip = jax.devices()[0]
    ref = forward(jax.device_put(params, one_chip), tokens)
    want = ref[prompt - 1:prompt + YI_DECODE_STEPS]
    gap = float(np.abs(np.stack(rows) - want).max() / np.abs(want).max())
    log(f"yi-9b 2 layers at published widths, float32 tp=4 prefill + "
        f"{YI_DECODE_STEPS} decode steps vs the plain float32 forward: max "
        f"rel diff {gap:.3e} (tolerance {LOGIT_RTOL:g})")
    check(gap <= LOGIT_RTOL, f"yi-9b tp=4 logits stray {gap:.3e}")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run phases C and D, on four chips")
    args = p.parse_args()
    if not os.environ.get("JAX_PLATFORMS"):
        os.environ["JAX_PLATFORMS"] = "tpu"     # before JAX: no CPU fallback
    sys.path[:0] = [os.path.join(HERE, "src"), os.path.join(HERE, "tests")]
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print("[smoke] no TPU: this run measures nothing", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    os.makedirs(RESULTS, exist_ok=True)
    phases = ([("C", phase_c), ("D", phase_d)] if args.four_chips
              else [("A", phase_a), ("B", phase_b)])
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            fn()
            log(f"phase {name} wall {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
