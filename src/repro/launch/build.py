"""Cell builder: (arch × shape × flags × mesh) → lowered/compiled XLA.

This is the single entry point shared by the dry-run, the JClient workload
adapter and the benchmarks.  Nothing here allocates device memory — all
inputs are ShapeDtypeStructs; ``.lower().compile()`` produces the artifact
the roofline/measurement layers read.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig, SHAPES, get_arch
from repro.core.tracing import span
from repro.models.model import BuildFlags, Model
from repro.parallel.sharding import ShardingPolicy
from repro.train.optimizer import adafactor, adamw, cosine_schedule
from repro.train.train_step import TrainStepConfig, make_train_step, train_state_shapes


def pick_optimizer(arch: ArchConfig, name: Optional[str] = None):
    """AdamW by default; Adafactor where AdamW state cannot fit a v5e pod
    (llama4-maverick-400b: 400e9 × 8 B fp32 slots > 4 TB pod HBM)."""
    if name is None:
        name = "adafactor" if arch.param_count() > 100e9 else "adamw"
    sched = cosine_schedule(3e-4, 2000, 100_000)
    return (adafactor(sched) if name == "adafactor" else adamw(sched)), name


@dataclasses.dataclass
class BuiltCell:
    kind: str
    lowered: Any
    compiled: Any
    n_devices: int
    meta: Dict[str, Any]


def _state_shardings(policy: ShardingPolicy, state_shapes):
    """Param-rule shardings for the whole train state (opt slots mirror the
    param paths, so the same path rules apply)."""
    return policy.param_shardings(state_shapes)


def build_cell(arch: ArchConfig, shape: ShapeConfig, mesh,
               flags: BuildFlags = BuildFlags(),
               tsc: TrainStepConfig = TrainStepConfig(),
               optimizer: Optional[str] = None,
               donate: bool = False,
               compile: bool = True) -> BuiltCell:
    with span("jx.build.lower", kind=shape.kind):
        policy = ShardingPolicy(mesh, sp=flags.sp, fsdp=flags.fsdp)
        model = Model(arch, flags, policy)
        n_dev = mesh.size
        meta: Dict[str, Any] = {"arch": arch.name, "shape": shape.name}

        if shape.kind == "train":
            opt, opt_name = pick_optimizer(arch, optimizer)
            meta["optimizer"] = opt_name
            step = make_train_step(model, opt, tsc, policy=policy)
            state_shapes = train_state_shapes(model, opt, tsc)
            state_sh = _state_shardings(policy, state_shapes)
            batch = model.input_specs(shape)
            batch_sh = policy.batch_shardings(batch)
            jfn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,) if donate else ())
            lowered = jfn.lower(state_shapes, batch)
        elif shape.kind == "prefill":
            batch = model.input_specs(shape)
            batch_sh = policy.batch_shardings(batch)
            params_shapes = model.init_shapes()
            params_sh = policy.param_shardings(params_shapes)
            jfn = jax.jit(model.prefill, in_shardings=(params_sh, batch_sh))
            lowered = jfn.lower(params_shapes, batch)
        elif shape.kind == "decode":
            params_shapes = model.init_shapes()
            params_sh = policy.param_shardings(params_shapes)
            cache_shapes = jax.eval_shape(
                lambda: model.empty_caches(shape.global_batch, shape.seq_len))
            cache_sh = policy.cache_shardings(cache_shapes)
            tokens = model.input_specs(shape)["tokens"]
            tok_sh = policy.sharding(policy.batch_spec(tokens.shape))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jfn = jax.jit(model.decode_step,
                          in_shardings=(params_sh, tok_sh, cache_sh,
                                        policy.replicated()),
                          out_shardings=(None, cache_sh),
                          donate_argnums=(2,) if donate else ())
            lowered = jfn.lower(params_shapes, tokens, cache_shapes, pos)
        else:
            raise ValueError(shape.kind)

    compiled = None
    if compile:
        # a load from the persistent compile cache counts here too
        with span("jx.build.compile", kind=shape.kind):
            compiled = lowered.compile()
    return BuiltCell(shape.kind, lowered, compiled, n_dev, meta)


# ---------------------------------------------------------------------------
# Generation workload (the paper's Llama2/LLaVA experiments): prefill of a
# prompt + N greedy decode steps against a max_len cache.
# ---------------------------------------------------------------------------


def build_generation(arch: ArchConfig, mesh, flags: BuildFlags = BuildFlags(),
                     batch: int = 1, prompt_len: int = 64, max_len: int = 256,
                     ) -> Tuple[BuiltCell, BuiltCell]:
    """Prefill and decode cells, sharded over heads as ``launch.serve`` runs
    the model (``sp`` off): a prompt's residual stream is too small for
    sequence parallelism to pay, and sequence-sharded attention on a
    tensor-parallel mesh gathers each layer's whole output projection."""
    flags = dataclasses.replace(flags, sp=False)
    pre = ShapeConfig("gen_prefill", "prefill", prompt_len, batch)
    dec = ShapeConfig("gen_decode", "decode", max_len, batch)
    return (build_cell(arch, pre, mesh, flags),
            build_cell(arch, dec, mesh, flags))
