"""The grouped matmul kernels (ops/grouped_matmul.py) against
`jax.lax.ragged_dot` and its `jax.vjp`, interpreted on the CPU, with tiles
small enough that a 64-row buffer holds four row tiles, two column tiles
and two steps of K."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import grouped_matmul as gm

ROWS, K, N = 64, 256, 256
# rows each of four groups holds, of a 64-row buffer in row tiles of 16
LOADS = {
    "boundary-inside-a-tile": (10, 23, 7, 9),
    "empty-group-first": (0, 20, 20, 10),
    "empty-groups-in-the-middle": (20, 0, 0, 25),
    "empty-group-last": (16, 30, 10, 0),
    "no-live-row": (0, 0, 0, 0),
    "every-row-live": (16, 16, 20, 12),
    "far-fewer-live-rows-than-the-buffer": (3, 0, 2, 0),
}
FORMS = {"rows-x-w": 0, "rows-x-wt": 1, "rowst-x-rows": 2}


def _lowered(path):
    return telemetry.default_registry().get_sample_value(
        "mxtpu_grouped_matmul_lowerings", {"path": path}) or 0.0


def _operands(load, k, n, dtype, dead):
    """Sorted rows, weights, a cotangent; `dead` fills the rows that no
    group holds."""
    rng = onp.random.default_rng(sum(load) + k)
    live = (onp.arange(ROWS) < sum(load))[:, None]
    rows, ct = (jnp.asarray(onp.where(live, rng.normal(size=(ROWS, w)), dead),
                            dtype) for w in (k, n))
    weights = jnp.asarray(rng.normal(size=(len(load), k, n)) * k ** -0.5,
                          dtype)
    return rows, weights, ct, jnp.asarray(live)


def _kernels_and_reference(case, dtype):
    """((result, rows' cotangent, weights' cotangent) of the seam, the same
    of `lax.ragged_dot`), dead rows zeroed.  The kernels' operands carry
    NaN in the rows of no group; the reference's carry zeros."""
    load = jnp.asarray(LOADS[case], jnp.int32)
    out = []
    for fn, dead in ((gm.grouped_matmul, onp.nan),
                     (functools.partial(jax.lax.ragged_dot,
                                        precision=gm._prec(dtype)), 0.0)):
        rows, weights, ct, live = _operands(LOADS[case], K, N, dtype, dead)
        y, vjp = jax.vjp(lambda a, b: fn(a, b, load), rows, weights)
        d_rows, d_weights = vjp(ct)
        assert (y.dtype, d_rows.dtype, d_weights.dtype) == (dtype,) * 3
        out.append(tuple(onp.asarray(x, onp.float32) for x in (
            jnp.where(live, y, 0), jnp.where(live, d_rows, 0), d_weights)))
    return out


# the three forms of one (case, dtype) come from one run, under small tiles
_under_small_tiles = functools.lru_cache(maxsize=None)(_kernels_and_reference)


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(gm, "_ROWS", 16)
    monkeypatch.setattr(gm, "_COLS", 128)
    monkeypatch.setattr(gm, "_DEPTH", 128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", LOADS)
@pytest.mark.parametrize("form", FORMS)
def test_kernels_equal_ragged_dot_and_its_vjp(small_tiles, form, case, dtype):
    got, want = (x[FORMS[form]] for x in _under_small_tiles(case, dtype))
    assert onp.isfinite(got).all()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    onp.testing.assert_allclose(got, want, rtol=tol,
                                atol=tol * max(1.0, onp.abs(want).max()))


def test_k_whole_and_one_column_tile_equal_ragged_dot_too():
    """The default tiles at these widths: K whole (no accumulator in the
    forward forms), one column tile, one row tile."""
    assert gm._tiling(ROWS, K, N) == (ROWS, K, N)
    got, want = _kernels_and_reference("boundary-inside-a-tile", jnp.float32)
    for g, w in zip(got, want):
        onp.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("empty_too", [False, True],
                         ids=["rows-forms", "weights-form"])
def test_grid_is_bounded_by_the_live_tiles_not_by_the_buffer(empty_too):
    """5 live rows of a 65,536-row buffer: one visit (the weights' form
    visits each empty group once more, to write its zeros), and every
    visit's tile and group are the ones that hold the rows."""
    load = jnp.asarray([3, 0, 2, 0], jnp.int32)
    offsets, group, tile, visits = gm._visits(load, 65536, 512, empty_too)
    assert [int(v) for v in offsets] == [0, 3, 3, 5, 5]
    assert int(visits) == (4 if empty_too else 2)
    assert [int(v) for v in group[:int(visits)]] == \
        ([0, 1, 2, 3] if empty_too else [0, 2])
    assert not onp.asarray(tile[:int(visits)]).any()
    assert group.shape == (65536 // 512 + 4 - 1,)
    # a full buffer, every boundary inside a tile: the most visits there are
    load = jnp.asarray([100, 65000, 400, 36], jnp.int32)
    _, group, tile, visits = gm._visits(load, 65536, 512, empty_too)
    assert int(visits) == 128 + 3
    assert [int(v) for v in tile[:3]] == [0, 0, 1]
    assert [int(v) for v in tile[int(visits) - 3:]] == [127, 127, 127]
    assert [int(v) for v in group[int(visits) - 3:]] == [1, 2, 3]


@pytest.mark.parametrize("k,n,path", [(128, 256, "pallas"), (8, 4, "ragged_dot"),
                                      (128, 100, "ragged_dot"),
                                      (100, 128, "ragged_dot")])
def test_widths_that_do_not_tile_take_ragged_dot(k, n, path):
    load = (10, 23, 7, 9)
    rows, weights, ct, live = _operands(load, k, n, jnp.float32, 0.0)
    load = jnp.asarray(load, jnp.int32)
    other = "ragged_dot" if path == "pallas" else "pallas"
    before = _lowered(path), _lowered(other)
    got, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(a, b, load),
                       rows, weights)
    assert (_lowered(path), _lowered(other)) == (before[0] + 1, before[1])
    want, want_vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(
            a, b, load, precision=jax.lax.Precision.HIGHEST), rows, weights)
    (d_rows, d_weights), (want_rows, want_weights) = vjp(ct), want_vjp(ct)
    for g, w in ((got, want), (d_rows, want_rows)):
        onp.testing.assert_allclose(jnp.where(live, g, 0),
                                    jnp.where(live, w, 0),
                                    rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(d_weights, want_weights, rtol=1e-5, atol=1e-5)


def test_the_decoder_configurations_widths_take_the_kernels():
    """Mellum2's hidden 2304 and expert width 896 (gate|up side by side:
    1792), a part's 65,536 sorted rows, 16 held experts, bf16."""
    load = jax.ShapeDtypeStruct((16,), jnp.int32)
    before = _lowered("pallas"), _lowered("ragged_dot")
    for k, n in ((2304, 1792), (896, 2304)):
        out = jax.eval_shape(
            gm.grouped_matmul, jax.ShapeDtypeStruct((65536, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((16, k, n), jnp.bfloat16), load)
        assert (out.shape, out.dtype) == ((65536, n), jnp.bfloat16)
    assert (_lowered("pallas"), _lowered("ragged_dot")) == \
        (before[0] + 2, before[1])
