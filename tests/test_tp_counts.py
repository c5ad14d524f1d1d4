"""Yi-9B's layout on a four-device tensor-parallel board, against plain
references that import nothing of the program (``tests/plain_llama.py``).

One subprocess with four host devices builds the program's generation
cells (``build_generation`` on a (1, 4) mesh, as a tp=4 board builds them)
at Yi-9B's head layout (GQA 8:4, scaled down) and

* runs its prefill, then decode through the cache, on seeded random
  weights: the logits at every step must match the plain float32 forward
  within 1e-4 of the largest |logit| (shard reduction order); the same
  program in bfloat16 is the control that must not;
* summarises the cells at 2, 4 and 8 layers: ``summarize``'s per-device
  wire bytes must equal the plain tp=4 count within 2% and its FLOPs
  within 5% (on XLA:CPU both are equal; the TPU lowers the RMSNorms' sums
  of squares as a multiply-and-reduce, which adds 0.02–0.04% there).

HLO text from the TPU compiler (a described v5e:2x2, trimmed) pins the
forms the CPU does not emit: a loop with no ``known_trip_count``, loops
nested, a reduce-scatter folded into a ``kind=kCustom`` fusion and an
all-gather cloned into an async collective fusion's computations.
"""
import json

import numpy as np
import pytest

from conftest import run_with_devices
from plain_llama import forward, tp_step_counts
from repro.roofline.analysis import HloCounts, collective_wire_bytes

SIZES = dict(n_heads=8, n_kv_heads=4, head_dim=16)   # Yi-9B's 8:1 GQA, 4-way
PROMPT, DECODE_STEPS = 16, 6
MAX_LEN = PROMPT + DECODE_STEPS + 5                  # 27: not a multiple of 4
LOGIT_RTOL = 1e-4
WIRE_RTOL, FLOPS_RTOL = 0.02, 0.05
DEPTHS = (2, 4, 8)

CODE = r"""
import json
import jax, numpy as np
from repro.configs import get_arch, reduced
from repro.launch.build import build_generation
from repro.launch.mesh import make_mesh_dp_tp
from repro.models import BuildFlags, Model
from repro.roofline.analysis import summarize

SIZES, PROMPT, STEPS, MAX_LEN, DEPTHS, OUT = {args}
mesh = make_mesh_dp_tp(1, 4)
arch = reduced(get_arch("yi-9b"), n_layers=2, **SIZES)
params = Model(arch, BuildFlags(dtype="float32")).init(jax.random.key(7))
tokens = np.asarray(jax.random.randint(jax.random.key(8), (PROMPT + STEPS,),
                                       0, arch.vocab_size), np.int32)
saved = {{"tokens": tokens}}
for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
    saved["param/" + "/".join(p.key for p in path)] = np.asarray(leaf)

for dtype in ("float32", "bfloat16"):
    pre, dec = build_generation(arch, mesh, BuildFlags(dtype=dtype), batch=1,
                                prompt_len=PROMPT, max_len=MAX_LEN)
    cast = jax.tree.map(lambda a: a.astype(dtype), params)
    p_sh, b_sh = pre.compiled.input_shardings[0]
    logits, caches = pre.compiled(jax.device_put(cast, p_sh), jax.device_put(
        {{"tokens": tokens[None, :PROMPT]}}, b_sh))
    rows = [np.asarray(logits, np.float32)[0]]
    pad = lambda c: np.pad(np.asarray(c), [(0, 0)] * (c.ndim - 3)
                           + [(0, MAX_LEN - PROMPT), (0, 0), (0, 0)])
    d_sh = dec.compiled.input_shardings[0]
    params_d = jax.device_put(cast, d_sh[0])
    caches = jax.device_put(jax.tree.map(pad, caches), d_sh[2])
    for j in range(STEPS):
        tok = jax.device_put(tokens[None, PROMPT + j:PROMPT + j + 1], d_sh[1])
        logits, caches = dec.compiled(params_d, tok, caches,
                                      jax.device_put(np.int32(PROMPT + j),
                                                     d_sh[3]))
        rows.append(np.asarray(logits, np.float32)[0])
    saved["logits/" + dtype] = np.stack(rows)
np.savez(OUT, **saved)

counts = {{}}
for layers in DEPTHS:
    a = reduced(get_arch("yi-9b"), n_layers=layers, **SIZES)
    pre, dec = build_generation(a, mesh, BuildFlags(dtype="float32"),
                                batch=1, prompt_len=PROMPT, max_len=MAX_LEN)
    for phase, cell in (("prefill", pre), ("decode", dec)):
        art = summarize(cell.compiled, 4)
        counts[f"{{layers}}/{{phase}}"] = dict(
            wire=art.wire_bytes_per_device, flops=art.flops_per_device,
            trips=list(art.loop_trips), unknown=art.unknown_trip_loops)
print(json.dumps({{"counts": counts, "vocab": arch.vocab_size,
                  "d_model": arch.d_model, "d_ff": arch.d_ff}}))
"""


@pytest.fixture(scope="module")
def tp4(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("tp4") / "run.npz")
    args = (SIZES, PROMPT, DECODE_STEPS, MAX_LEN, DEPTHS, out)
    stdout = run_with_devices(CODE.format(args=repr(args)), n_devices=4)
    info = json.loads(stdout.strip().splitlines()[-1])
    with np.load(out) as z:
        info["arrays"] = {k: z[k] for k in z.files}
    return info


def _params(arrays):
    tree = {}
    for key, val in arrays.items():
        if key.startswith("param/"):
            *parents, leaf = key[len("param/"):].split("/")
            node = tree
            for p in parents:
                node = node.setdefault(p, {})
            node[leaf] = val
    return tree


@pytest.mark.parametrize("dtype,within", [("float32", True),
                                          ("bfloat16", False)])
def test_tp4_prefill_and_decode_match_the_plain_forward(tp4, dtype, within):
    arrays = tp4["arrays"]
    ref = forward(_params(arrays), arrays["tokens"])
    got = arrays["logits/" + dtype]
    want = ref[PROMPT - 1:PROMPT + DECODE_STEPS]
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    assert (gap <= LOGIT_RTOL) == within, (dtype, gap)


@pytest.mark.parametrize("layers", DEPTHS)
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_tp4_counts_match_the_plain_count_per_trip(tp4, layers, phase):
    got = tp4["counts"][f"{layers}/{phase}"]
    wire, flops = tp_step_counts(
        layers=layers, d_model=tp4["d_model"], d_ff=tp4["d_ff"],
        vocab=tp4["vocab"], tokens=PROMPT if phase == "prefill" else 1,
        context=PROMPT if phase == "prefill" else MAX_LEN, tp=4,
        act_bytes=4, **SIZES)
    assert got["trips"] == [layers] and got["unknown"] == 0
    assert got["wire"] == pytest.approx(wire, rel=WIRE_RTOL)
    assert got["flops"] == pytest.approx(flops, rel=FLOPS_RTOL)


# -- HLO text of the TPU compiler (v5e:2x2), trimmed -------------------------

# lax.scan of 5 around lax.scan of 3, the inner body all-reducing an
# f32[8,256] matmul output: the inner collective runs 15 times
NESTED_WHILE = """
HloModule jit_f, entry_computation_layout={(f32[8,64]{1,0}, f32[5,64,256]{2,1,0})->f32[8,64]{1,0}}

%fused_computation.2.clone.clone (param_0.8: f32[8,64], param_1.8: bf16[64,256]) -> f32[8,256] {
  %param_0.8 = f32[8,64]{1,0:T(8,128)} parameter(0)
  %param_1.8 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.3 = f32[8,256]{1,0:T(8,128)} convolution(%param_0.8, %param_1.8), dim_labels=bf_io->bf
}

%add.clone (x.3: f32[], y.1: f32[]) -> f32[] {
  %x.3 = f32[]{:T(128)} parameter(0)
  %y.1 = f32[]{:T(128)} parameter(1)
  ROOT %add.5 = f32[]{:T(128)} add(%x.3, %y.1)
}

%wide.region_1.1_spmd.sunk (wide.param.5: (s32[], f32[8,64], bf16[64,256])) -> (s32[], f32[8,64], bf16[64,256]) {
  %constant.38..sunk.1..sunk = s32[]{:T(128)} constant(1)
  %wide.param.5 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[64,256]{1,0:T(8,128)(2,1)S(1)}) parameter(0)
  %get-tuple-element.100 = s32[]{:T(128)} get-tuple-element(%wide.param.5), index=0
  %get-tuple-element.101 = f32[8,64]{1,0:T(8,128)S(1)} get-tuple-element(%wide.param.5), index=1
  %get-tuple-element.102 = bf16[64,256]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%wide.param.5), index=2
  %add.11 = s32[]{:T(128)} add(%get-tuple-element.100, %constant.38..sunk.1..sunk)
  %fusion.8 = f32[8,256]{1,0:T(8,128)} fusion(%get-tuple-element.101, %get-tuple-element.102), kind=kOutput, calls=%fused_computation.2.clone.clone
  %all-reduce.2 = f32[8,256]{1,0:T(8,128)S(1)} all-reduce(%fusion.8), channel_id=1, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.clone
  %slice.1 = f32[8,64]{1,0:T(8,128)S(1)} slice(%all-reduce.2), slice={[0:8], [0:64]}
  ROOT %tuple.28 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[64,256]{1,0:T(8,128)(2,1)S(1)}) tuple(%add.11, %slice.1, %get-tuple-element.102)
}

%wide.region_2.2_spmd (wide.param.1: (s32[], f32[8,64], bf16[64,256])) -> pred[] {
  %wide.param.1 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[64,256]{1,0:T(8,128)(2,1)S(1)}) parameter(0)
  %constant.34 = s32[]{:T(128)} constant(3)
  %get-tuple-element.54 = s32[]{:T(128)} get-tuple-element(%wide.param.1), index=0
  ROOT %lt.6 = pred[]{:T(512)} compare(%get-tuple-element.54, %constant.34), direction=LT
}

%wide.region_0.3_spmd.sunk (wide.param.4: (s32[], f32[8,64], bf16[5,64,256])) -> (s32[], f32[8,64], bf16[5,64,256]) {
  %constant.25..sunk = s32[]{:T(128)} constant(0)
  %constant.38..sunk = s32[]{:T(128)} constant(1)
  %wide.param.4 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)}) parameter(0)
  %get-tuple-element.117 = s32[]{:T(128)} get-tuple-element(%wide.param.4), index=0
  %get-tuple-element.118 = f32[8,64]{1,0:T(8,128)S(1)} get-tuple-element(%wide.param.4), index=1
  %get-tuple-element.119 = bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%wide.param.4), index=2
  %add.10 = s32[]{:T(128)} add(%get-tuple-element.117, %constant.38..sunk)
  %dynamic-slice.3 = bf16[1,64,256]{2,1,0:T(8,128)(2,1)} dynamic-slice(%get-tuple-element.119, %get-tuple-element.117, %constant.25..sunk, %constant.25..sunk), dynamic_slice_sizes={1,64,256}
  %bitcast.7 = bf16[64,256]{1,0:T(8,128)(2,1)} bitcast(%dynamic-slice.3)
  %copy.5 = s32[]{:T(128)} copy(%constant.25..sunk)
  %tuple.30 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[64,256]{1,0:T(8,128)(2,1)S(1)}) tuple(%copy.5, %get-tuple-element.118, %bitcast.7)
  %while.25 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[64,256]{1,0:T(8,128)(2,1)S(1)}) while(%tuple.30), condition=%wide.region_2.2_spmd, body=%wide.region_1.1_spmd.sunk
  %get-tuple-element.120 = f32[8,64]{1,0:T(8,128)S(1)} get-tuple-element(%while.25), index=1
  ROOT %tuple.31 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)}) tuple(%add.10, %get-tuple-element.120, %get-tuple-element.119)
}

%wide.region_3.4_spmd (wide.param.3: (s32[], f32[8,64], bf16[5,64,256])) -> pred[] {
  %wide.param.3 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)}) parameter(0)
  %constant.43 = s32[]{:T(128)} constant(5)
  %get-tuple-element.67 = s32[]{:T(128)} get-tuple-element(%wide.param.3), index=0
  ROOT %lt.7 = pred[]{:T(512)} compare(%get-tuple-element.67, %constant.43), direction=LT
}

ENTRY %main.5_spmd (param.4: f32[8,64], param.5: f32[5,64,256]) -> f32[8,64] {
  %param.4 = f32[8,64]{1,0:T(8,128)} parameter(0)
  %param.5 = f32[5,64,256]{2,1,0:T(8,128)} parameter(1)
  %constant.25 = s32[]{:T(128)} constant(0)
  %convert.1 = bf16[5,64,256]{2,1,0:T(8,128)(2,1)} convert(%param.5)
  %copy.6 = s32[]{:T(128)} copy(%constant.25)
  %tuple.37 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)}) tuple(%copy.6, %param.4, %convert.1)
  %while.24 = (s32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}, bf16[5,64,256]{2,1,0:T(8,128)(2,1)S(1)}) while(%tuple.37), condition=%wide.region_3.4_spmd, body=%wide.region_0.3_spmd.sunk
  ROOT %get-tuple-element.130 = f32[8,64]{1,0:T(8,128)S(1)} get-tuple-element(%while.24), index=1
}
"""

# Yi-9B's sequence-parallel prefill at tp=4 (3 layers): the layer body
# gathers wo (bf16[1,32,128,4096]) through an async collective fusion, whose
# start, step and done computations each hold a clone of the all-gather
# (channel 23), and reduce-scatters the FFN output through a kCustom fusion
# of an all-reduce and a dynamic-slice
FUSED_TPU = """
HloModule jit_prefill

%add.1.clone (x.3: bf16[], y.3: bf16[]) -> bf16[] {
  %x.3 = bf16[]{:T(256)} parameter(0)
  %y.3 = bf16[]{:T(256)} parameter(1)
  ROOT %add.40 = bf16[]{:T(256)} add(%x.3, %y.3)
}

%all-reduce-scatter.clone.clone (input.2: bf16[64,4096]) -> bf16[16,4096] {
  %input.2 = bf16[64,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.8 = bf16[64,4096]{1,0:T(8,128)(2,1)} all-reduce(%input.2), channel_id=26, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add.1.clone
  %constant.435 = u32[] constant(16)
  %partition-id.1 = u32[] partition-id()
  %multiply.31 = u32[] multiply(%constant.435, %partition-id.1)
  %constant.436 = u32[] constant(0)
  ROOT %dynamic-slice.87 = bf16[16,4096]{1,0:T(8,128)(2,1)S(1)} dynamic-slice(%all-reduce.8, %multiply.31, %constant.436), dynamic_slice_sizes={16,4096}
}

%fused_computation.116 (param_0.399: bf16[1,8,128,4096]) -> (bf16[1,8,128,4096], bf16[1,32,128,4096], s32[2]) {
  %param_0.399 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.52 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)} all-gather(%param_0.399), channel_id=23, replica_groups=[1,4]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %custom-call.9 = (bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) custom-call(%all-gather.52), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.140 (param_0.404: bf16[1,8,128,4096], param_1.382: bf16[1,32,128,4096]) -> (bf16[1,8,128,4096], bf16[1,32,128,4096]) {
  %param_0.404 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(0)
  %all-gather.54 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} all-gather(%param_0.404), channel_id=23, replica_groups=[1,4]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %tuple.43 = (bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}) tuple(%param_0.404, %all-gather.54)
}

%fused_computation.120 (param_0.422: bf16[1,8,128,4096], param_1.392: bf16[1,32,128,4096]) -> bf16[1,32,128,4096] {
  %param_0.422 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.392 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(1)
  %all-gather.60 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)} all-gather(%param_0.422), channel_id=23, replica_groups=[1,4]<=[4], dimensions={1}, use_global_device_ids=true
  ROOT %custom-call.11 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%param_0.422, %param_1.392, %all-gather.60), custom_call_target="AsyncCollectiveDone"
}

%wide.region_0.5_spmd.sunk (wide.param.2: (s32[], bf16[1,16,4096], bf16[3,8,128,4096])) -> (s32[], bf16[1,16,4096], bf16[3,8,128,4096]) {
  %constant.191..sunk = s32[]{:T(128)} constant(1)
  %wide.param.2 = (s32[]{:T(128)}, bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.252 = s32[]{:T(128)} get-tuple-element(%wide.param.2), index=0
  %get-tuple-element.276 = bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)} get-tuple-element(%wide.param.2), index=2
  %constant_dynamic-slice_fusion.7 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%get-tuple-element.276, %get-tuple-element.252), dynamic_slice_sizes={1,8,128,4096}
  %async-collective-start.1 = (bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) fusion(%constant_dynamic-slice_fusion.7), kind=kCustom, calls=%fused_computation.116
  %get-tuple-element.301 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%async-collective-start.1), index=0
  %get-tuple-element.302 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%async-collective-start.1), index=1
  %fusion.140 = (bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}, bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)}) fusion(%get-tuple-element.301, %get-tuple-element.302), kind=kCustom, calls=%async_collective_fusion.140
  %get-tuple-element.376 = bf16[1,8,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%fusion.140), index=0
  %get-tuple-element.377 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%fusion.140), index=1
  %async-collective-done.1 = bf16[1,32,128,4096]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.376, %get-tuple-element.377), kind=kCustom, calls=%fused_computation.120
  %fusion.130 = bf16[64,4096]{1,0:T(8,128)(2,1)} bitcast(%async-collective-done.1)
  %fusion.131 = bf16[16,4096]{1,0:T(8,128)(2,1)S(1)} fusion(%fusion.130), kind=kCustom, calls=%all-reduce-scatter.clone.clone
  %add.92 = s32[]{:T(128)} add(%get-tuple-element.252, %constant.191..sunk)
  %bitcast_add_fusion.2 = bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)} bitcast(%fusion.131)
  ROOT %tuple.39 = (s32[]{:T(128)}, bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)}) tuple(%add.92, %bitcast_add_fusion.2, %get-tuple-element.276)
}

%wide.region_5.6_spmd (wide.param.1: (s32[], bf16[1,16,4096], bf16[3,8,128,4096])) -> pred[] {
  %constant.371 = s32[]{:T(128)} constant(3)
  %wide.param.1 = (s32[]{:T(128)}, bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.146 = s32[]{:T(128)} get-tuple-element(%wide.param.1), index=0
  ROOT %lt.15 = pred[]{:T(512)} compare(%get-tuple-element.146, %constant.371), direction=LT
}

ENTRY %main.9_spmd (param.8: bf16[3,8,128,4096], param.3: bf16[1,16,4096]) -> bf16[1,16,4096] {
  %param.8 = bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %param.3 = bf16[1,16,4096]{2,1,0:T(8,128)(2,1)} parameter(1)
  %constant.163 = s32[]{:T(128)} constant(0)
  %copy.45 = s32[]{:T(128)} copy(%constant.163)
  %tuple.37 = (s32[]{:T(128)}, bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)}) tuple(%copy.45, %param.3, %param.8)
  %while.13 = (s32[]{:T(128)}, bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[3,8,128,4096]{3,2,1,0:T(8,128)(2,1)}) while(%tuple.37), condition=%wide.region_5.6_spmd, body=%wide.region_0.5_spmd.sunk
  ROOT %get-tuple-element.1 = bf16[1,16,4096]{2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%while.13), index=1
}
"""

WO = 32 * 128 * 4096 * 2                          # bf16[1,32,128,4096]
FFN_OUT = 64 * 4096 * 2                           # bf16[64,4096]


@pytest.mark.parametrize("text,trips,unknown,wire,flops", [
    (NESTED_WHILE, [5, 3], 0,
     {"all-reduce": 15 * 2 * 8 * 256 * 4 * 3 / 4}, 15 * 2 * 8 * 64 * 256),
    (FUSED_TPU, [3], 0,
     {"all-gather": 3 * WO * 3 / 4, "reduce-scatter": 3 * FFN_OUT * 3 / 4},
     0.0),
    # a loop whose count the parser cannot read keeps the once-per-program
    # count, and says so
    (FUSED_TPU.replace("direction=LT", "direction=NE"), [1], 1,
     {"all-gather": WO * 3 / 4, "reduce-scatter": FFN_OUT * 3 / 4}, 0.0),
], ids=["nested-while", "fused-collectives", "unknown-trips"])
def test_tpu_hlo_counts_once_per_trip(text, trips, unknown, wire, flops):
    hlo = HloCounts(text, 4)
    assert sorted(hlo.loop_trips, reverse=True) == trips
    assert hlo.unknown_trip_loops == unknown
    got = hlo.collective_wire_bytes()
    assert {k: v for k, v in got.items() if k != "total"} == pytest.approx(wire)
    assert got["total"] == pytest.approx(sum(wire.values()))
    assert hlo.flops() == pytest.approx(flops)
    assert collective_wire_bytes(text, 4) == got
