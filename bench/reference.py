"""The benchmark's yardstick: plain references that import nothing of the
program under test.

* ``gp_posterior``       a float64 Gaussian-process posterior by Cholesky
  solves, the model the searcher's surrogate is specified as (RBF kernel,
  targets standardised per fit);
* ``measure_generation`` the roofline time, modelled power and peak memory
  of one generation workload (prefill + ``n_tok`` decode steps), recomputed
  from an artifact's counts and ``peaks.json``;
* ``param_bytes``        the parameter bytes one device holds, counted
  from a configuration file's sizes;
* ``hypervolume_2d``     the exact 2-D hypervolume of a minimised front.

Each takes a ``dtype``: float64 is the reference, a lower precision is the
control that ``bench/control.py`` shows to fail.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['chips'])}")
    return dict(table["chips"][device_kind], power=table["power_model"])


# -- search: plain GP posterior ----------------------------------------------

def encode(knobs: dict, space: Sequence) -> np.ndarray:
    """Ordinal value index over (len - 1) per knob: the search coordinates.
    ``space`` is a list of ``(name, values)`` in the space's order."""
    return np.asarray([list(vals).index(knobs[name]) / max(len(vals) - 1, 1)
                       for name, vals in space], np.float64)


def gp_posterior(x, y, xq, lengthscale, noise, signal, dtype=np.float64):
    """Posterior mean and standard deviation at ``xq`` of an RBF-kernel GP
    on observations ``(x, y)``, with ``y`` standardised by its mean and
    standard deviation (a constant target keeps unit scale)."""
    x, y, xq = (np.asarray(a, dtype) for a in (x, y, xq))
    ls2 = dtype(lengthscale) ** 2

    def kern(a, b):
        d2 = np.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
        return dtype(signal) * np.exp(dtype(-0.5) * d2 / ls2)

    ym = y.mean()
    ys = y.std()
    ys = ys if ys > 0 else dtype(1)
    k = kern(x, x) + dtype(noise) * np.eye(len(x), dtype=dtype)
    low = np.linalg.cholesky(k)
    alpha = np.linalg.solve(low.T, np.linalg.solve(low, (y - ym) / ys))
    ks = kern(xq, x)
    v = np.linalg.solve(low, ks.T)
    var = np.clip(dtype(signal) - np.sum(v * v, axis=0), dtype(1e-9), None)
    return ks @ alpha * ys + ym, np.sqrt(var) * ys


# -- measure: roofline of a generation workload -------------------------------

def _phase(art: dict, n: int, peak, bw, ici, clock, hbm_scale, pw, dtype):
    """Seconds and modelled per-chip power of one compiled program."""
    flops = dtype(art["flops_per_device"]) * dtype(art["n_devices"])
    hbm = dtype(art["hbm_bytes_per_device"]) * dtype(art["n_devices"])
    wire = dtype(art["wire_bytes_per_device"]) * dtype(art["n_devices"])
    t_c = flops / (n * peak)
    t_m = hbm / (n * bw)
    t = max(t_c, t_m, wire / (n * ici))
    if t <= 0:
        return t, dtype(pw["idle_w"])
    util_c = min(t_c / t, dtype(1))
    util_m = min(t_m / t, dtype(1))
    p = (dtype(pw["idle_w"])
         + dtype(pw["compute_w"]) * dtype(float(clock) ** 2.5) * util_c
         + dtype(pw["hbm_w"]) * hbm_scale * util_m)
    return t, p


def measure_generation(pre: dict, dec: dict, n_tok: int, n_chips: int,
                       knobs: dict, peaks: dict, dtype=np.float64) -> Dict:
    """``time_s``, ``power_w`` and ``mem_bytes`` of prefill + ``n_tok``
    decode steps at the hardware-ladder knobs (bf16 peak x clock scale, HBM
    and ICI bandwidth x their scales)."""
    clock = dtype(knobs["clock_scale"])
    hbm_scale = dtype(knobs["hbm_scale"])
    n = dtype(n_chips)
    peak = dtype(peaks["flops_bf16"]) * clock
    bw = dtype(peaks["hbm_bw"]) * hbm_scale
    ici = dtype(peaks["ici_bw_per_link"]) * dtype(knobs["ici_scale"])
    args = (n, peak, bw, ici, knobs["clock_scale"], hbm_scale,
            peaks["power"], dtype)
    t_p, p_p = _phase(pre, *args)
    t_d, p_d = _phase(dec, *args)
    k = dtype(n_tok)
    total_t = t_p + k * t_d
    energy = p_p * n * t_p + p_d * n * k * t_d
    mem = max(pre["arg_bytes"] + pre["temp_bytes"] + pre["output_bytes"],
              dec["arg_bytes"] + dec["temp_bytes"] + dec["output_bytes"])
    return {"time_s": total_t, "power_w": energy / (n * total_t),
            "mem_bytes": dtype(mem)}


# -- build: parameter bytes per device ----------------------------------------

def vocab_rows(model: dict) -> int:
    """Rows of the embedding: the vocabulary padded up to a multiple of
    ``pad_vocab_size_multiple`` where the configuration states one."""
    m = int(model.get("pad_vocab_size_multiple", 1))
    return -(-int(model["vocab_size"]) // m) * m


# the configuration file's names for a layer's mixer and feed-forward part
MIXERS = ("attention", "attention_window", "mamba2")
FFNS = ("dense", "moe", "none")
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def layer_kinds(model: dict) -> List[Tuple[str, str]]:
    """``(mixer, ffn)`` of each layer, as the file's ``layers`` list gives
    them, or else by ``kind``: ``transformer`` is attention and a dense FFN
    on every layer, ``mamba2`` a Mamba-2 mixer alone.  The first
    ``first_k_dense_replace`` layers take a dense FFN where they name
    ``moe``."""
    n = model["num_hidden_layers"]
    if "layers" in model:
        kinds = [(layer["mixer"], layer["ffn"]) for layer in model["layers"]]
    elif model["kind"] == "mamba2":
        kinds = [("mamba2", "none")] * n
    elif model["kind"] == "transformer":
        kinds = [("attention", "dense")] * n
    else:
        raise ValueError(f"kind {model['kind']!r} needs a layers list")
    if len(kinds) != n:
        raise ValueError(f"{len(kinds)} layers listed, "
                         f"num_hidden_layers is {n}")
    for mixer, ffn in kinds:
        if mixer not in MIXERS or ffn not in FFNS:
            raise ValueError(f"unknown layer kind ({mixer!r}, {ffn!r})")
    k = model.get("first_k_dense_replace", 0)
    return [(m, "dense" if f == "moe" and i < k else f)
            for i, (m, f) in enumerate(kinds)]


def ffn_act(model: dict) -> str:
    return model.get("mlp_hidden_act", model.get("hidden_act", "silu"))


def ffn_matrix_params(model: dict, width: int) -> int:
    """One gated feed-forward block of ``width``: gate, up and down."""
    return 3 * model["hidden_size"] * width


def mamba_sizes(model: dict) -> Dict[str, int]:
    """Inner width (``expand`` x the hidden size), state, heads and
    convolution of a Mamba-2 mixer with one group of B/C projections."""
    head = model.get("mamba_head_dim", model.get("head_dim"))
    di = model["expand"] * model["hidden_size"]
    n = model.get("ssm_state_size", model.get("state_size"))
    return {"di": di, "n": n, "h": di // head, "conv": model["conv_kernel"]}


def param_bytes(model: dict, tp: int, bytes_per_param: float = 2.0) -> float:
    """Parameter bytes one device of a ``tp``-way board holds: matrices split
    ``tp`` ways, vectors whole, counted layer by layer (``layer_kinds``).
    ``model`` holds the configuration file's sizes.  The mixer's small
    float32 vectors (Mamba-2's A_log, D and dt bias) keep 4 bytes whatever
    ``bytes_per_param`` is, and an MoE router keeps the bytes of its
    ``router_dtype`` (float32 unless the file states another), whole on
    each device; the shared experts are ``n_shared_experts`` of
    ``moe_intermediate_size``."""
    d, v = model["hidden_size"], vocab_rows(model)
    mats, vecs, whole = 0, 0, 0            # whole: bytes at their own type
    for mixer, ffn in layer_kinds(model):
        if mixer in ("attention", "attention_window"):
            hq, hkv, dh = (model["num_attention_heads"],
                           model["num_key_value_heads"], model["head_dim"])
            mats += 2 * d * hq * dh + 2 * d * hkv * dh
            vecs += d                          # norm
        elif mixer == "mamba2":
            m = mamba_sizes(model)
            di, h, bc = m["di"], m["h"], 2 * m["n"]
            mats += d * (2 * di + bc + h) + di * d
            mats += (di + bc) * m["conv"]
            vecs += di + bc + di + d           # conv biases, gated norm, norm
            whole += 4 * 3 * h
        if ffn == "dense":
            mats += ffn_matrix_params(model, model["intermediate_size"])
            vecs += d
        elif ffn == "moe":
            f = model["moe_intermediate_size"]
            experts = model.get("n_routed_experts", model.get("num_experts"))
            shared = model.get("n_shared_experts", 0) * f
            mats += (experts * ffn_matrix_params(model, f)
                     + ffn_matrix_params(model, shared))
            vecs += d
            whole += (d * experts
                      * DTYPE_BYTES[model.get("router_dtype", "float32")])
    vecs += d                                  # final norm
    mats += v * d * (1 if model["tie_word_embeddings"] else 2)
    return (mats / tp + vecs) * bytes_per_param + whole


def prefill_input_bytes(batch: int, prompt: int) -> int:
    """The prompt a prefill program takes: int32 token ids."""
    return batch * prompt * 4


# -- hypervolume ---------------------------------------------------------------

def nondominated(points: np.ndarray) -> np.ndarray:
    """Rows no other row dominates (minimisation); duplicates all kept."""
    p = np.asarray(points, float)
    le = np.all(p[:, None, :] <= p[None, :, :], axis=2)
    lt = np.any(p[:, None, :] < p[None, :, :], axis=2)
    return ~np.any(le & lt, axis=0)


def hypervolume_2d(points, ref) -> float:
    """Area dominated by ``points`` and bounded by ``ref`` (minimisation)."""
    pts = np.asarray(points, float).reshape(-1, 2)
    ref = np.asarray(ref, float)
    pts = pts[np.all(pts < ref, axis=1)]
    if len(pts) == 0:
        return 0.0
    pts = pts[nondominated(pts)]
    pts = pts[np.argsort(pts[:, 0])]
    hv, prev_y = 0.0, ref[1]
    for x, y in pts:
        hv += (ref[0] - x) * (prev_y - y)
        prev_y = y
    return float(hv)
