"""Compile the training path's Pallas kernels for a TPU v5e that is
*described*, not attached: what Mosaic or the SPMD partitioner would refuse
on the chip, it refuses here, at no chip time.

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
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from mxnet_tpu import context as mx_context
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


# M x C of BatchNorm's backward in ResNet-50 at batch 128: the stem output,
# a stage-2 block and the last stage (the widest and the narrowest M)
@pytest.mark.parametrize("m,c", [(128 * 112 * 112, 64), (128 * 28 * 28, 512),
                                 (128 * 7 * 7, 2048)])
def test_bn_bwd_reduce_compiles(one_chip, m, c):
    x = jax.ShapeDtypeStruct((m, c), F32, sharding=one_chip)
    n, _ = _custom_calls(
        functools.partial(nn_ops.bn_bwd_reduce_pallas, interpret=False), x, x)
    assert n == 1


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["mask", "mask+dropout"])
def test_flash_fwd_bwd_compiles(one_chip, dropout):
    """The configuration `benchmark/bert_pretrain_bench.py` trains at
    B=4, T=2048: key-padding mask, attention dropout in-kernel."""
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


def test_stem_kernel_compiles(one_chip):
    """bf16 operands under the package's float32 matmul default: the dot
    needs the kernels' own precision rule to lower at all."""
    xs = jax.ShapeDtypeStruct((128, 12, 112, 112), BF16, sharding=one_chip)
    wf = jax.ShapeDtypeStruct((64, 12, 4, 4), BF16, sharding=one_chip)
    n, _ = _custom_calls(
        functools.partial(stem.stem_conv_pallas, interpret=False), xs, wf)
    assert n == 1


def test_bn_backward_partitions_over_dp_mesh(dp_mesh, monkeypatch):
    """BatchNorm's backward inside a program sharded over four chips, as a
    `FusedTrainStep(recipe="dp4")` of ResNet-50 traces it.  A bare
    pallas_call there is refused ("Mosaic kernels cannot be automatically
    partitioned"); under the step's mesh_scope it runs per device and the
    two sums are all-reduced."""
    # take the TPU side of the kernel-vs-XLA rule: the process computes on
    # the CPU, the program is compiled for the chip
    monkeypatch.setattr(mx_context, "on_tpu", lambda: True)
    rows = NamedSharding(dp_mesh, P("dp"))
    rep = NamedSharding(dp_mesh, P())
    x = jax.ShapeDtypeStruct((128, 64, 56, 56), BF16, sharding=rows)
    ch = jax.ShapeDtypeStruct((64,), F32, sharding=rep)

    def loss(x, gamma, beta, mean, var):
        out, _, _ = nn_ops.batch_norm_train(x, gamma, beta, 0.9, 1e-5, 1,
                                            mean, var)
        return out.astype(F32).sum()

    def grad():  # a new function each time: jit caches traces by function
        return jax.grad(loss, argnums=(0, 1, 2))

    with mesh_scope(dp_mesh, ("dp",)):
        n, text = _custom_calls(grad(), x, ch, ch, ch, ch)
    assert n == 1
    assert "all-reduce" in text
    # the bug this guards: the same program with no scope declared
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _custom_calls(grad(), x, ch, ch, ch, ch)


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
