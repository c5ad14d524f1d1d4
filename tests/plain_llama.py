"""Plain references for a Llama-layout decoder, importing nothing of the
program under test.

* ``forward``   logits at every position of a GQA decoder (RMSNorm, RoPE
  on the two halves of each head, causal grouped-query attention, SwiGLU,
  untied head) in float32 under the highest matmul precision, with no
  cache, no kernels and no sharding.  It reads the weights as the nested
  dicts the program's ``init`` returns: ``embed/table`` (V, D), ``head/w``
  (D, V), ``final_norm/scale`` and, stacked over layers, ``scan/l0/mixer``
  (``norm/scale``, ``wq``/``wk``/``wv`` (D, H, dh), ``wo`` (H, dh, D)) and
  ``scan/l0/ffn`` (``norm/scale``, ``mlp/wi_gate``, ``mlp/wi_up`` (D, F),
  ``mlp/wo`` (F, D)).
* ``tp_step_counts``  the per-device collective wire bytes and matmul FLOPs
  of one tensor-parallel prefill or decode step of that decoder, with its
  heads, feed-forward width and vocabulary split ``tp`` ways (Megatron
  layout): each layer all-reduces its attention output and its
  feed-forward output, and the vocabulary-split embedding lookup
  all-reduces the looked-up rows.  A ring all-reduce moves
  2 · bytes · (tp − 1) / tp per device.  Elementwise work is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: (S, H, dh); rotate the first and second half of each head."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, *, eps=1e-6, theta=10_000.0):
    """Logits (S, V) of one sequence ``tokens`` (S,), in float32."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    layers = p["scan"]["l0"]
    n_layers = layers["mixer"]["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["table"][jnp.asarray(tokens)]
        s = x.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(n_layers):
            att = jax.tree.map(lambda a: a[i], layers["mixer"])
            ffn = jax.tree.map(lambda a: a[i], layers["ffn"])
            h = _rmsnorm(x, att["norm"]["scale"], eps)
            q = _rope(jnp.einsum("sd,dhk->shk", h, att["wq"]), theta)
            k = _rope(jnp.einsum("sd,dhk->shk", h, att["wk"]), theta)
            v = jnp.einsum("sd,dhk->shk", h, att["wv"])
            rep = q.shape[1] // k.shape[1]
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            logits = jnp.einsum("qhk,shk->hqs", q, k) / np.sqrt(q.shape[-1])
            w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
            o = jnp.einsum("hqs,shk->qhk", w, v)
            x = x + jnp.einsum("qhk,hkd->qd", o, att["wo"])
            h = _rmsnorm(x, ffn["norm"]["scale"], eps)
            mlp = ffn["mlp"]
            g = jax.nn.silu(h @ mlp["wi_gate"]) * (h @ mlp["wi_up"])
            x = x + g @ mlp["wo"]
        x = _rmsnorm(x, p["final_norm"]["scale"], eps)
        return np.asarray(x @ p["head"]["w"])


def tp_step_counts(*, layers, d_model, n_heads, n_kv_heads, head_dim, d_ff,
                   vocab, tokens, context, tp, act_bytes):
    """Per-device ``(wire_bytes, matmul_flops)`` of one step of a batch-1
    sequence over ``tp`` devices: ``tokens`` new positions (the prompt for
    a prefill, 1 for a decode step) attending over ``context`` positions
    (the prompt, or the whole cache, masked positions included), logits at
    the last position only, activations all-reduced at ``act_bytes``."""
    all_reduce = 2 * tokens * d_model * act_bytes * (tp - 1) / tp
    wire = (2 * layers + 1) * all_reduce
    qkvo = 2 * tokens * d_model * (2 * n_heads + 2 * n_kv_heads) * head_dim
    attend = 2 * 2 * tokens * context * n_heads * head_dim
    ffn = 3 * 2 * tokens * d_model * d_ff
    head = 2 * d_model * vocab
    flops = (layers * (qkvo + attend + ffn) + head) / tp
    return wire, flops
