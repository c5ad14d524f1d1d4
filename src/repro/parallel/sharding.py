"""Sharding policy: DP/FSDP over ``data`` (× ``pod``), TP over ``model``,
SP on the residual stream, EP for MoE experts, sequence-sharded KV caches.

Rules are path-based over the param pytree.  Dims that don't divide the axis
size fall back to GSPMD's padded (uneven) sharding — jit/SPMD supports this;
the padding waste (e.g. llama4's 40 q-heads on a 16-way model axis) is
visible in the roofline table and discussed in DESIGN.md.

Decode KV caches are sharded over the *sequence* axis of the cache on the
``model`` axis (flash-decode/split-K adapted to the mesh): attention logits
are computed on sequence shards, and XLA SPMD inserts the small all-reduces
for the softmax statistics and the weighted-value sum.  This is what makes
``long_500k`` (batch=1) scale — batch parallelism is unavailable, sequence
parallelism isn't.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class ShardingPolicy:
    def __init__(self, mesh: Mesh, *, fsdp: bool = True, sp: bool = True):
        self.mesh = mesh
        names = mesh.axis_names
        self.tp_axis = "model" if "model" in names else None
        data_axes = tuple(a for a in ("pod", "data") if a in names)
        self.dp_axes: Tuple[str, ...] = data_axes
        self.fsdp = fsdp
        self.sp = sp

    # -- helpers ---------------------------------------------------------------
    def _axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            if a is not None:
                n *= self.mesh.shape[a]
        return n

    def _fits(self, dim: int, axes) -> Optional[Any]:
        """Use the axis only if it divides the dim exactly — jit in_shardings
        require even tiling.  Non-divisible dims (llama4's 40 q-heads on a
        16-way model axis, glm4's 2 kv-heads) replicate on that dim; the
        surrounding dims still shard, see DESIGN.md §6."""
        if axes is None:
            return None
        if dim % self._axis_size(axes) == 0:
            return axes
        return None

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    @property
    def dp(self):
        return self.dp_axes if self.dp_axes else None

    @property
    def fsdp_axes(self):
        return self.dp_axes if (self.fsdp and self.dp_axes) else None

    # -- param rules -------------------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """Right-aligned trailing-dim rules; leading axes (e.g. the scanned
        (n_groups,) stack axis) are never sharded."""
        tp, fs = self.tp_axis, self.fsdp_axes
        f = self._fits

        def right(*trailing) -> P:
            return P(*([None] * (len(shape) - len(trailing)) + list(trailing)))

        if "embed" in path and path.endswith("table"):        # (V, D)
            return right(f(shape[-2], tp), f(shape[-1], fs))
        if path.endswith("head/w"):                            # (D, V)
            return right(f(shape[-2], fs), f(shape[-1], tp))
        if path.endswith("frontend/proj"):
            return right(f(shape[-2], fs), f(shape[-1], tp))
        if re.search(r"mixer/w[qkv]$", path):                  # (D, H, dh)
            return right(f(shape[-3], fs), f(shape[-2], tp), None)
        if path.endswith("mixer/wo"):                          # (H, dh, D)
            return right(f(shape[-3], tp), None, f(shape[-1], fs))
        if re.search(r"(mlp|shared)/wi_(gate|up)$", path):     # (D, F)
            return right(f(shape[-2], fs), f(shape[-1], tp))
        if re.search(r"(mlp|shared)/wo$", path):               # (F, D)
            return right(f(shape[-2], tp), f(shape[-1], fs))
        if re.search(r"experts/wi_(gate|up)$", path):          # (E, D, Fe)
            return right(f(shape[-3], tp), f(shape[-2], fs), None)
        if path.endswith("experts/wo"):                        # (E, Fe, D)
            return right(f(shape[-3], tp), None, f(shape[-1], fs))
        if path.endswith("router"):                            # (D, E)
            return right(f(shape[-2], fs), None)
        if re.search(r"mixer/w(z|x|b|c|dt)$", path) or path.endswith("out_proj"):
            return right(f(shape[-2], fs), f(shape[-1], tp))  # mamba (D, X)
        if re.search(r"conv_[xbc]$", path):                    # (C, K)
            return right(f(shape[-2], tp), None)
        # 1-D norms / biases / A_log etc: replicate
        return P()

    def param_shardings(self, params_treedef_shapes) -> Any:
        """Map a pytree of ShapeDtypeStructs/arrays → NamedShardings."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_treedef_shapes)
        out = []
        for path, leaf in flat:
            spath = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            out.append(self.sharding(self.param_spec(spath, leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    def param_specs_tree(self, params_shapes) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
        out = []
        for path, leaf in flat:
            spath = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            out.append(self.param_spec(spath, leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- activation constraints (used inside the model) ----------------------------
    def constrain_residual(self, x):
        """(B, S, D) residual stream: batch over data; seq over model if SP."""
        if x.ndim != 3:
            return x
        b, s, _ = x.shape
        bspec = self._fits(b, self.dp)
        sspec = self._fits(s, self.tp_axis) if (self.sp and s > 1) else None
        return jax.lax.with_sharding_constraint(
            x, self.sharding(P(bspec, sspec, None)))

    def constrain_attn_q(self, q):
        """(B, S, H, dh): seq-sharded over model with SP (K/V stay gathered);
        otherwise shard heads over model when divisible."""
        b, sq, h, _ = q.shape
        if self.sp and sq > 1:
            spec = P(self._fits(b, self.dp), self._fits(sq, self.tp_axis), None, None)
        else:
            spec = P(self._fits(b, self.dp), None, self._fits(h, self.tp_axis), None)
        return jax.lax.with_sharding_constraint(q, self.sharding(spec))

    def constrain_attn_kv(self, k):
        """(B, S, Hkv, dh): replicated over model under SP (GQA K/V are small);
        head-sharded when SP is off and the head count divides."""
        b, skv, hkv, _ = k.shape
        if self.sp and skv > 1:
            spec = P(self._fits(b, self.dp), None, None, None)
        else:
            spec = P(self._fits(b, self.dp), None, self._fits(hkv, self.tp_axis), None)
        return jax.lax.with_sharding_constraint(k, self.sharding(spec))

    def constrain_logits(self, x):
        b = x.shape[0]
        v = x.shape[-1]
        spec = [self._fits(b, self.dp)] + [None] * (x.ndim - 2) + [self._fits(v, self.tp_axis)]
        return jax.lax.with_sharding_constraint(x, self.sharding(P(*spec)))

    def constrain_expert_buffer(self, buf):
        """(g, E, C, D) — groups over data, experts over model (device-local
        dispatch grid); legacy 3-D (E, C, D) shards experts only."""
        if buf.ndim == 4:
            spec = P(self._fits(buf.shape[0], self.dp),
                     self._fits(buf.shape[1], self.tp_axis), None, None)
        else:
            spec = P(self._fits(buf.shape[0], self.tp_axis), None, None)
        return jax.lax.with_sharding_constraint(buf, self.sharding(spec))

    def constrain_group_local(self, t):
        """(g, …): sharded on the group (data) dim only — scatter/gather on
        the trailing dims are then provably device-local per group."""
        spec = P(self._fits(t.shape[0], self.dp), *([None] * (t.ndim - 1)))
        return jax.lax.with_sharding_constraint(t, self.sharding(spec))

    def moe_groups(self, batch: int) -> int:
        """Group-local MoE dispatch group count (= data-parallel degree)."""
        n = self._axis_size(self.dp)
        return n if (n > 1 and batch % n == 0) else 1

    def constrain_tokens_for_moe(self, x):
        """(B, S, D) purely batch-sharded (groups must own contiguous rows)."""
        return jax.lax.with_sharding_constraint(
            x, self.sharding(P(self._fits(x.shape[0], self.dp), None, None)))

    # -- data / cache shardings ------------------------------------------------------
    def batch_spec(self, leaf_shape: Tuple[int, ...]) -> P:
        b = leaf_shape[0]
        return P(self._fits(b, self.dp), *([None] * (len(leaf_shape) - 1)))

    def batch_shardings(self, batch) -> Any:
        return jax.tree.map(lambda l: self.sharding(self.batch_spec(l.shape)), batch)

    def cache_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """KV caches (…, B, S, Hkv, dh): seq-shard over model, batch over data.
        Mamba caches: batch over data, heads/channels over model.  A leading
        (n_groups,) scan axis may be present."""
        lead = len(shape) - 4
        if path.endswith("/k") or path.endswith("/v"):
            b, s, hkv, dh = shape[lead:]
            bspec = self._fits(b, self.dp)
            # batch=1 (long_500k): fold the idle data/pod axes into the
            # sequence sharding so all 256/512 chips hold cache shards.
            seq_axes = (self.tp_axis,) if bspec is not None else (
                tuple(self.dp_axes) + (self.tp_axis,))
            seq_axes = tuple(a for a in seq_axes if a is not None) or None
            sspec = self._fits(s, seq_axes)
            # a cache length the model axis does not divide (a prompt plus
            # its generated tokens) shards the KV heads instead, as the
            # decode step's K/V projections are sharded
            hspec = None if sspec is not None else self._fits(hkv, self.tp_axis)
            return P(*([None] * lead), bspec, sspec, hspec, None)
        if path.endswith("state"):                    # (B, H, P, N)
            b, h = shape[lead], shape[lead + 1]
            return P(*([None] * lead), self._fits(b, self.dp),
                     self._fits(h, self.tp_axis), None, None)
        if path.endswith("conv"):                     # (B, K-1, C)
            lead = len(shape) - 3
            b, _, c = shape[lead:]
            return P(*([None] * lead), self._fits(b, self.dp), None,
                     self._fits(c, self.tp_axis))
        return P()

    def cache_shardings(self, caches) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
        out = []
        for path, leaf in flat:
            spath = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            out.append(self.sharding(self.cache_spec(spath, leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    def replicated(self) -> NamedSharding:
        return self.sharding(P())
