"""End-to-end serving driver: batched greedy generation.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama-1.1b --reduced --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="tinyllama-1.1b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--dtype", default="float32")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None):
    """Generate once; returns the arch and the ``GenerationResult``."""
    args = parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import BuildFlags, Model
    from repro.serve import Engine

    enable_compile_cache()

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    model = Model(arch, BuildFlags(dtype=args.dtype, remat="none", sp=False))
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)

    batch = {}
    ptoks = args.prompt_len
    if arch.frontend == "vision":
        f = arch.n_frontend_tokens
        batch["image_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, f, arch.d_model), dtype=np.float32))
        ptoks = max(args.prompt_len - f, 1)
    if arch.frontend == "audio":
        batch["frame_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, ptoks, arch.d_model), dtype=np.float32))
    else:
        batch["tokens"] = jnp.asarray(
            rng.integers(0, arch.vocab_size, (args.batch, ptoks)), jnp.int32)

    eng = Engine(model, params, max_len=args.prompt_len + args.gen + 1)
    t0 = time.time()
    res = eng.generate(batch, args.gen)
    dt = time.time() - t0
    print(f"[serve] arch={arch.name} batch={args.batch} prompt={res.n_prompt} "
          f"generated={res.n_generated} in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("[serve] first sequence:", res.tokens[0][:16].tolist())
    return arch, res


if __name__ == "__main__":
    main()
