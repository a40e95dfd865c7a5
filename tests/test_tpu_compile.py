"""Compile the training path's Pallas kernels, BatchNorm's backward, BERT's
FFN and a two-layer cut of the BERT cell's step for a TPU v5e that is
*described*, not attached: what Mosaic or the SPMD partitioner would refuse
on the chip, it refuses here, and what the TPU compiler would materialise,
it shows here, at no chip time.

Nothing runs, so nothing here is a result or a time — `chip_smoke.py` is the
run.  The CPU mesh never lowers these kernels (off-TPU they are interpreted
and the XLA forms are dispatched), which is how a kernel that cannot lower
and a step that cannot be partitioned both got into the tree.

The topology is described inside the `topo` fixture and nowhere else: only
one process may load libtpu, every xdist worker imports this module, and the
worker that is handed the file keeps the library until it exits — so the
compiles run in the test's own process and all live in this one file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from mxnet_tpu.analysis import census
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import stem
from mxnet_tpu.parallel.mesh import mesh_scope

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip (the next run warns and compiles
    # again), so the cache is off around this module's compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dp_mesh(topo):
    return Mesh(onp.asarray(topo.devices).reshape(4), ("dp",))


def _custom_calls(fn, *args):
    """Compile ``fn`` for the shardings its arguments carry; the number of
    Mosaic kernels in the compiled program, and its text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call"), text


def _bn_grad():
    """grad of a training-mode NCHW BatchNorm's summed output w.r.t. (x,
    gamma, beta) — a new function on every call: jit caches traces by
    function."""
    def loss(x, gamma, beta, mean, var):
        out, _, _ = nn_ops.batch_norm_train(x, gamma, beta, 0.9, 1e-5, 1,
                                            mean, var)
        return out.astype(F32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


def _f32_relayouts(text, elements):
    """Names of the `copy`, `transpose` and `reshape` instructions of a
    compiled program whose result is an f32 array of ``elements`` elements."""
    found = []
    for name, dims, op in re.findall(
            r"%([\w.\-]+) = f32\[([\d,]+)\]\S* (copy|transpose|reshape)\(",
            text):
        if math.prod(int(d) for d in dims.split(",")) == elements:
            found.append(f"{op} {name} f32[{dims}]")
    return found


# N, C, H, W of BatchNorm's backward in ResNet-50 at batch 128: the stem
# output, a stage-2 block and the last stage (the widest and the narrowest
# M = N*H*W).  The guard of PR 26's gain: the two sums are XLA's fused
# reduction over dy and the activation as the step holds them — no Mosaic
# kernel, and no f32 copy of an activation laid out again to feed one
@pytest.mark.parametrize("shape", [(128, 64, 112, 112), (128, 512, 28, 28),
                                   (128, 2048, 7, 7)],
                         ids=["1605632-64", "100352-512", "6272-2048"])
def test_bn_backward_writes_no_f32_activation(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    ch = jax.ShapeDtypeStruct(shape[1:2], F32, sharding=one_chip)
    n, text = _custom_calls(_bn_grad(), x, ch, ch, ch, ch)
    assert n == 0
    assert _f32_relayouts(text, math.prod(shape)) == []


def _fusions(text):
    """`[(result type, operand types, the fused computation's instructions)]`
    of the entry computation's fusions in a compiled program's text, types
    without layouts."""
    comps, entry, _, _ = census._parse_computations(text)
    types = {i.name: re.sub(r"\{[^}]*\}", "", i.result) for i in comps[entry]}
    return [(types[i.name], [types[n] for n in i.operand_names],
             comps[census._CALLS_RE.search(i.attrs).group(1)])
            for i in comps[entry] if i.opcode == "fusion"]


def _opcodes(instructions, dims):
    """Opcodes of the instructions whose result is one array of ``dims``."""
    return [i.opcode for i in instructions
            if re.match(r"\w+\[" + dims + r"\]", i.result)]


def test_ffn_gelu_epilogue_is_one_erf(one_chip):
    """BERT-base's FFN at the benchmark cell's shapes, bf16, between its two
    LayerNorms as an encoder layer has it: Dense(768→3072) → exact GELU →
    Dense(3072→768) + residual, forward and backward.  The guard of PR 29's
    gain: GELU rides in the FFN-up matmul's epilogue as one native `erf` (the
    `erfc` form XLA expands there, with two divides and an exponential over
    the whole tensor, took longer than the matmul) and is written once, not
    evaluated again in each consumer's operands; the backward fusion rebuilds
    GELU' from the pre-activation alone."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    def loss(x, g1, b1, w_up, b_up, w_down, b_down, g2, b2):
        x = nn_ops.layer_norm(x, g1, b1, eps=1e-12)
        h = nn_ops.fully_connected(x, w_up, b_up, flatten=False)
        h = nn_ops.leaky_relu(h, act_type="gelu")
        h = nn_ops.fully_connected(h, w_down, b_down, flatten=False)
        out = nn_ops.layer_norm(h + x, g2, b2, eps=1e-12)
        return (out.astype(F32) ** 2).sum()

    n, text = _custom_calls(
        jax.grad(loss, argnums=tuple(range(9))), spec(128, 128, 768),
        spec(768), spec(768), spec(3072, 768), spec(3072), spec(768, 3072),
        spec(768), spec(768), spec(768))
    assert n == 0
    wide = "bf16[128,128,3072]"
    matmuls = [(out, operands, _opcodes(body, "128,128,3072"))
               for out, operands, body in _fusions(text)]
    matmuls = [f for f in matmuls if "convolution" in f[2]]
    up = [f for f in matmuls if "bf16[3072]" not in f[0]]
    back = [f for f in matmuls if "bf16[3072]" in f[0]]
    assert len(up) == 1 and len(back) == 1, [f[:2] for f in matmuls]

    assert up[0][0] == f"({wide}, {wide})"      # x and GELU(x), nothing else
    assert up[0][2].count("erf") == 1
    assert not {"exponential", "divide", "select"} & set(up[0][2]), up[0][2]

    assert back[0][0] == f"(bf16[3072], {wide})"
    assert back[0][1].count(wide) == 1, back[0][1]      # x, not x and GELU(x)
    assert back[0][2].count("erf") == 1
    # and nowhere else: not in the operands of the FFN-down matmul forward,
    # nor of its weight gradient
    assert len(re.findall(r"= f32\[128,128,3072\]\S* erf\(", text)) == 2


def _bert_cell_step(one_chip, layers, batch, seq):
    """The benchmark's `bert_base` step (`chipbench/configs/bert_base`: MLM +
    NSP loss, Adam, one `FusedTrainStep`, dense attention at this length)
    cut to ``layers`` layers, compiled for the described chip from the shapes
    of the arguments the step itself prepares."""
    import mxnet_tpu as mx
    from chipbench import run as bench
    from chipbench.configs import bert_base

    cfg = dict(bench.load_json(bench.HERE, "configs", "bert_base.json"),
               num_hidden_layers=layers)
    fused = mx.gluon.FusedTrainStep(*bert_base.build(cfg))
    ints = tuple(mx.np.zeros((batch, seq), dtype="int32") for _ in range(4))
    *arrays, clip, treedef_id = fused._prepare(ints, batch)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        arrays)
    return fused._jit.lower(*shapes, clip, treedef_id).compile()


def test_dropout_mask_is_written_once(one_chip):
    """The guard of PR 31's gain, on the benchmark cell's own step at batch
    128, T=128 and two layers (a bare Dense → dropout → residual does not
    show the fault): every dropout mask's random words -- or, for the
    attention masks, their one relayout `copy` -- are read by exactly one
    fusion, the compare that writes the mask as `pred`, and by nothing else.
    Without the barrier in `ops.nn.dropout` XLA repeats the compare in a
    backward fusion, so the hidden-size words (4 bytes a slot, not 1) are
    copied out of the fast memory to HBM to outlive the forward pass and
    sliced back: 4 `copy-start`s of `u32[128,128,768]` here, 24 in the cell."""
    compiled = _bert_cell_step(one_chip, layers=2, batch=128, seq=128)
    comps, entry, _, _ = census._parse_computations(compiled.as_text())
    entry = comps[entry]

    def readers(name):
        return [i for i in entry if name in i.operand_names]

    draws = [i for i in entry if i.opcode == "rng-bit-generator"]
    shapes = sorted(i.result.split("{")[0] for i in draws)
    # embedding, and per layer: attention output and FFN; per layer: attention
    assert shapes == ["u32[128,12,128,128]"] * 2 + ["u32[128,128,768]"] * 5
    for draw in draws:
        read_by = readers(draw.name)
        if [i.opcode for i in read_by] == ["copy"]:     # the relayout
            read_by = readers(read_by[0].name)
        assert [i.opcode for i in read_by] == ["fusion"], \
            (draw.name, [(i.opcode, i.name) for i in read_by])
        assert read_by[0].result.startswith("pred["), read_by[0].result
    assert not [i.name for i in entry
                if i.opcode == "copy-start" and "u32[128,128,768]" in i.result]


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["mask", "mask+dropout"])
def test_flash_fwd_bwd_compiles(one_chip, dropout):
    """What `chip_smoke.py`'s BERT step launches at B=4, T=2048:
    key-padding mask, attention dropout in-kernel."""
    qkv = jax.ShapeDtypeStruct((4, 12, 2048, 64), BF16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def loss(q, k, v, mask, seed):
        out = pk.flash_attention(q, k, v, mask=mask, dropout=dropout,
                                 key=seed, interpret=False)
        return out.astype(F32).sum()

    n, _ = _custom_calls(jax.grad(loss, argnums=(0, 1, 2)),
                         qkv, qkv, qkv, mask, seed)
    assert n == 3  # forward, dq, dk+dv


@pytest.mark.parametrize("window", [1024, None], ids=["window-1024", "full"])
def test_grouped_query_flash_compiles_at_the_decoder_cells_shapes(one_chip, window):
    """What `mellum2_12b_a2p5b.sft_t8192_ep4share` launches in a layer: 32
    query heads over 4 key-value heads of 128, T=8192, bf16, the window
    layers' tile and the full layers'.  Each kernel carries its name."""
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), BF16, sharding=one_chip)

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True, window=window,
                                 interpret=False)
        return out.astype(F32).sum()

    n, text = _custom_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert n == 3  # forward, dq, dk+dv
    assert all(name in text for name in ("flash_fwd", "flash_bwd_dq",
                                         "flash_bwd_dkv"))


def test_two_size_flash_compiles_at_the_latent_attention_cells_shapes(one_chip):
    """What `kimi_linear_48b_a3b.sft_t16384_ep32share` launches in its MLA
    layer: 32 heads, q and k 192 wide (128 + 64), v 128 wide, one sequence
    of 16,384, bf16, full causal.  The three kernels take both sizes: the
    outputs' and cotangents' shapes say which operand got which."""
    q = jax.ShapeDtypeStruct((1, 32, 16384, 192), BF16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 32, 16384, 128), BF16, sharding=one_chip)

    def loss(q, k, v):
        out = pk.flash_attention(q, k, v, causal=True, interpret=False)
        assert out.shape == v.shape
        return out.astype(F32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    assert [g.shape[-1] for g in jax.eval_shape(grad, q, q, v)] == [192, 192, 128]
    n, text = _custom_calls(grad, q, q, v)
    assert n == 3  # forward, dq, dk+dv
    assert all(name in text for name in ("flash_fwd", "flash_bwd_dq",
                                         "flash_bwd_dkv"))


def test_kda_core_compiles_at_the_cells_shapes(one_chip, monkeypatch):
    """KDA's core at (1, 16384, 32, 128) bf16, forward and backward, on the
    kernel path, its operands views of (B, T, H K) arrays as the layer's are:
    the first phase is `kda_chunk_fwd` (in the forward loop and again where
    the backward pass recomputes a group) and `kda_chunk_bwd`, which read
    q, k, v, g where they lie (no copy to chunk-major, none to any other
    layout); no triangular solve, no f32 [.., 64, 64] tensor and no
    16 x 16 x 128 ratio is XLA's to write any more.  Still a `while` over the
    four groups of 64 chunks each way and, inside, one over a group's chunks
    (forward, recomputed, backward: five in all), a state kept per group
    and, while a group is differentiated, per chunk of it, never T/64 of
    them at once; the temporaries are half of the XLA form's 2.22 GB."""
    from mxnet_tpu.ops import linear_attention as la
    monkeypatch.setattr(la._context, "on_tpu", lambda: True)
    t, h, d = 16384, 32, 128
    qkv = jax.ShapeDtypeStruct((1, t, h * d), BF16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, t, h * d), F32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, t, h), F32, sharding=one_chip)

    def loss(q, k, v, g, beta):
        q, k, v, g = (x.reshape(1, t, h, d) for x in (q, k, v, g))
        return (la.kda(q, k, v, g, beta).astype(F32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qkv, qkv, qkv, g, beta).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(kda_chunk_\w+?)[.\d]* = \S.* custom-call\(", text)
    assert sorted(calls) == ["kda_chunk_bwd", "kda_chunk_fwd", "kda_chunk_fwd"]
    assert text.count("tpu_custom_call") == 3 and "riangular" not in text
    assert text.count(" while(") == 5
    assert f"f32[4,1,{h},{d},{d}]" in text                   # a state a group
    assert f"f32[64,1,{h},{d},{d}]" in text                  # a state a chunk of one group
    assert f"f32[{t // 64},1,{h},{d},{d}]" not in text
    assert f"[{t},{h},{d},{d}]" not in text and f"[{t},1,{h},{d},{d}]" not in text
    assert not re.search(rf"f32\[[\d,]*1,{h},64,64\]", text)   # A, B, the inverse: chunk-major
    assert not re.search(r"\[[\d,]*16,16,128\]", text)
    # q, k, v, g and their cotangents stay (B, T, H K): nothing of their size
    # is copied, transposed or reshaped, whole or a group of 4096 tokens
    moved = re.findall(r"= (\w+)\[([\d,]+)\]\S* (?:copy|transpose|reshape)\(", text)
    assert [m for m in moved if math.prod(map(int, m[1].split(","))) >= 4096 * h * d] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9


def test_the_head_and_loss_hold_no_f32_log_softmax(one_chip):
    """The Kimi cell's head and loss, value and gradient, at (1, 16383,
    20480) on 2304 units: the target is picked before the log-sum-exp, so no
    f32 [1, 16383, 20480] array of log-probabilities is written for the loss
    VALUE to read after the backward pass (2.01 GB of temporaries; 2.68 with
    the log-softmax left to autodiff, 1.34 of it held through a step)."""
    from mxnet_tpu.models import decoder
    t, u, vocab = 16383, 2304, 20480
    x = jax.ShapeDtypeStruct((1, t, u), BF16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((vocab, u), BF16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=one_chip)

    def loss(x, w, ids):
        return decoder._next_token_nll(jnp.einsum("btu,vu->btv", x, w), ids)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, w, ids).compile()
    assert f"f32[1,{t},{vocab}]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9


def test_mhc_passes_compile_at_the_xing_cells_shapes(one_chip, monkeypatch):
    """One mHC sublayer of the Xing cell, 8,192 tokens on four streams of
    3,584 in bf16, value and gradient, the streams flat as `DecoderLayer.run`
    hands them over: the pre-mix and the combine are `mhc_pre_fwd` /
    `mhc_pre_bwd` and `mhc_post_fwd` / `mhc_post_bwd`, once each; XLA writes
    no f32 stream-sized tensor and moves none between layouts (no copy, no
    transpose, no physical reshape), and the combine's backward writes no dX
    of its own (the fold): 430 MB of temporaries, about the kernels' own
    outputs."""
    from mxnet_tpu.models import decoder
    from mxnet_tpu.ops import hyper_connection as hc
    monkeypatch.setattr(hc._context, "on_tpu", lambda: True)
    t, n, u = 8192, 4, 3584

    def post_and_res(z, alpha, bias):
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
        res = decoder.sinkhorn(jnp.clip(alpha[2] * z[..., 2 * n:] + bias[2 * n:], -30.0,
                                        30.0).reshape(z.shape[:-1] + (n, n)), 20, 1e-6)
        return post, res

    def sublayer(flat, w, alpha, bias):
        x = flat.reshape(1, t, n, u)
        h, post, res, streams = hc.mixes(x, w, alpha, bias, post_and_res, 1e-6)
        out = hc.combine(streams, h * 2, post, res, folded=True)
        return (out.reshape(flat.shape).astype(F32) ** 2).sum()

    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip) for shape, dt in (
        ((1, t, n * u), BF16), ((n * u, 2 * n + n * n), BF16), ((3,), F32),
        ((2 * n + n * n,), F32))]
    compiled = jax.jit(jax.grad(sublayer, argnums=(0, 1, 2, 3))).lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(mhc_\w+?)[.\d]* = \S.* custom-call\(", text)
    assert sorted(calls) == ["mhc_post_bwd", "mhc_post_fwd", "mhc_pre_bwd", "mhc_pre_fwd"]
    comps, entry, _, _ = census._parse_computations(text)
    big = [(m.group(1), i.opcode) for i in comps[entry]
           for m in [re.match(r"(\w+)\[([\d,]+)\]", i.result)]
           if m and math.prod(map(int, m.group(2).split(","))) == t * n * u]
    assert not [op for _dt, op in big if op in ("copy", "transpose", "reshape")], big
    assert not [op for dt, op in big if dt == "f32"], big
    # the streams' gradient is written once, by `mhc_pre_bwd`; the loss's own
    # cotangent of X' is the one other stream-sized value XLA writes
    assert sum(op == "fusion" for _dt, op in big) <= 1, big
    assert compiled.memory_analysis().temp_size_in_bytes < 6e8


# held experts, F, and a limit on this layer's `temp_size_in_bytes`.  The tree
# before PR 37, whose XLA work ran over the whole buffers, read 1,746,002,944
# and 1,743,809,024.  The Kimi cell's shapes are held to that (1,727,043,072
# now; that cell stands at 15.6 of 16.9 GB); the Mellum cell's read 1,798,396,416
# standing alone and are held to that, while its whole step reads what it did
# (5,367,403,520 against 5,367,086,080; Kimi's 7,888,997,376 against
# 8,040,897,536: scratch compiles of the steps for the described chip, PR 37)
@pytest.mark.parametrize("held,f,temp_limit", [(16, 896, 1800000000),
                                               (8, 1024, 1743809024)],
                         ids=["mellum-16x896", "kimi-8x1024"])
def test_routed_experts_lower_to_grouped_matmul_kernels(
        one_chip, monkeypatch, held, f, temp_limit):
    """bf16 rows through the held experts at the two decoder cells' widths
    and 16,384 tokens: the grouped matmuls and their gradients are this
    repository's Mosaic kernels (`ops/grouped_matmul.py`; none is left on
    `lax.ragged_dot`, no transposed copy of the weights is made) over
    buffers that hold a part's worst case; one straight-line body in a loop
    over the parts, and nothing scatters.  What XLA runs on the sorted rows
    between the kernels runs in loops over the live blocks: outside a
    kernel a whole-part tensor of sorted rows is only ever a loop's carried
    buffer, updated in place a block at a time."""
    from mxnet_tpu.ops import grouped_matmul as gm
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(gm._context, "on_tpu", lambda: True)
    n, k, u = 16384, 8, 2304
    m = jax.ShapeDtypeStruct((n, u), BF16, sharding=one_chip)
    top_e = jax.ShapeDtypeStruct((n, k), jnp.int32, sharding=one_chip)
    top_w = jax.ShapeDtypeStruct((n, k), F32, sharding=one_chip)
    w_in = jax.ShapeDtypeStruct((held, u, f), BF16, sharding=one_chip)
    w_out = jax.ShapeDtypeStruct((held, f, u), BF16, sharding=one_chip)

    def loss(m, top_e, top_w, gate, up, down):
        y, _load = moe.routed_experts(m, top_e, top_w, gate, up, down, 16)
        return (y.astype(F32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4, 5))).lower(
        m, top_e, top_w, w_in, w_in, w_out).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 8 and "ragged-dot" not in text
    # 2 forward, 2 again + 2 + 2 backward, by the kernels' `name=`
    calls = re.findall(r"ragged_(\w+?)_*[.\d]* = \S+ custom-call\(", text)
    assert sorted(calls) == ["gmm"] * 4 + ["gmm_t"] * 2 + ["tgmm"] * 2
    picks = moe.PICKS_AT_ONCE
    assert f"bf16[{picks},{u}]" in text               # every pick of a part
    assert f"bf16[{n * k},{u}]" not in text
    assert f"bf16[{held},{2 * f},{u}]" not in text    # no weights transposed
    assert " while(" in text and " conditional(" not in text
    assert " scatter(" not in text
    # every instruction of every computation, fused ones included, whose
    # result is one whole-part array of sorted rows
    comps = census._parse_computations(text)[0]
    whole = re.compile(rf"\w+\[{picks},({u}|{2 * f}|{f})\]")
    made_by = [i.opcode for body in comps.values() for i in body
               if whole.match(i.result)]
    assert set(made_by) <= {"parameter", "get-tuple-element", "custom-call",
                            "fusion", "dynamic-update-slice"}, set(made_by)
    rows = moe._pick_block(picks, moe.ROWS_AT_ONCE)
    assert f"bf16[{rows},{u}]" in text and f"bf16[{rows},{2 * f}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= temp_limit


@pytest.mark.parametrize("rows,k,n", [(48, 128, 256), (1040, 256, 4608),
                                      (4096, 4608, 128)],
                         ids=["one-small-tile", "columns-split", "k-split"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_grouped_matmul_lowers_at_the_edges_of_its_rule(
        one_chip, monkeypatch, dtype, rows, k, n):
    """Every shape `grouped_matmul` sends to its kernels has to lower: rows
    in multiples of 16 only, N and K past one tile (an accumulator over K)."""
    from mxnet_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm._context, "on_tpu", lambda: True)
    a = jax.ShapeDtypeStruct((rows, k), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, k, n), dtype, sharding=one_chip)
    ct = jax.ShapeDtypeStruct((rows, n), dtype, sharding=one_chip)
    load = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)

    def three(a, w, load, ct):
        y, vjp = jax.vjp(lambda a, w: gm.grouped_matmul(a, w, load), a, w)
        return (y,) + vjp(ct)

    n_calls, _ = _custom_calls(three, a, w, load, ct)
    assert n_calls == 3


def test_stem_kernel_compiles(one_chip):
    """bf16 operands under the package's float32 matmul default: the dot
    needs the kernels' own precision rule to lower at all."""
    xs = jax.ShapeDtypeStruct((128, 12, 112, 112), BF16, sharding=one_chip)
    wf = jax.ShapeDtypeStruct((64, 12, 4, 4), BF16, sharding=one_chip)
    n, _ = _custom_calls(
        functools.partial(stem.stem_conv_pallas, interpret=False), xs, wf)
    assert n == 1


def test_bn_backward_partitions_over_dp_mesh(dp_mesh):
    """BatchNorm's backward inside a program sharded over four chips, as a
    `FusedTrainStep(recipe="dp4")` of ResNet-50 traces it: the two sums are
    over the global batch, a sharded axis, so XLA's partitioner all-reduces
    them — no kernel to wrap, with or without the step's mesh_scope."""
    rows = NamedSharding(dp_mesh, P("dp"))
    rep = NamedSharding(dp_mesh, P())
    x = jax.ShapeDtypeStruct((128, 64, 56, 56), BF16, sharding=rows)
    ch = jax.ShapeDtypeStruct((64,), F32, sharding=rep)

    with mesh_scope(dp_mesh, ("dp",)):
        scoped = _custom_calls(_bn_grad(), x, ch, ch, ch, ch)
    for n, text in (scoped, _custom_calls(_bn_grad(), x, ch, ch, ch, ch)):
        assert n == 0
        assert "all-reduce" in text


def test_flash_partitions_over_dp_mesh(dp_mesh):
    """`flash_attention` as a mesh-sharded BERT step calls it: one launch
    per device over its share of the batch."""
    rows = NamedSharding(dp_mesh, P("dp"))
    rep = NamedSharding(dp_mesh, P())
    qkv = jax.ShapeDtypeStruct((4, 12, 2048, 64), BF16, sharding=rows)
    mask = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=rows)
    seed = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    def loss(q, k, v, mask, seed):
        out = pk.flash_attention(q, k, v, mask=mask, dropout=0.1, key=seed,
                                 interpret=False)
        return out.astype(F32).sum()

    with mesh_scope(dp_mesh, ("dp",)):
        n, _ = _custom_calls(jax.grad(loss, argnums=(0, 1, 2)),
                             qkv, qkv, qkv, mask, seed)
    assert n == 3
