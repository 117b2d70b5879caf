"""Per-tile scaling without relayouting the payload (`core/quant._tiled_op`).

`_tiled_op` applies each tile's scale by splitting every tiled axis but the
last and repeating the scale along the last (lane) axis.  The oracle below
is the form it replaced, which split every tiled axis (the last included)
into (n/t, t): the two must agree bit for bit, for the dequantize (bf16 and
f32) and for the quantize's division.  The structural tests keep the
payload's last axis out of every reshape, in the jaxpr of the dequantize of
an expert weight and of a W8-resident `we13` leaf.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fp8 import E4M3, FMT_MAX, TILE
from repro.core.quant import (QTensor, _dequantize_nocount, _split_shape,
                              compute_scale, quantize)
from repro.serve.w8 import quantize_params_for_serving, retile

# (tile, tiles along each tiled axis): row-wise tiles with one tile along
# the last axis take the repeated-scale form, with several the split form
CASES = [((1, TILE, TILE), 2), ((TILE, TILE), 2), ((1, 1, TILE), 2),
         ((1, TILE), 2), ((1, 1, TILE), 1), ((1, TILE), 1)]


def _oracle_tiled_op(x, scale, tile, op):
    """Every tiled axis split as (n/t, t), the last one included."""
    xs, ss = _split_shape(x.shape, tile)
    return op(x.reshape(xs), scale.reshape(ss)).reshape(x.shape)


def _oracle_dequantize(q, dtype):
    if dtype == jnp.bfloat16:
        return _oracle_tiled_op(q.data.astype(jnp.bfloat16),
                                q.scale.astype(jnp.bfloat16), q.tile,
                                lambda a, b: a * b)
    return _oracle_tiled_op(q.data.astype(jnp.float32), q.scale, q.tile,
                            lambda a, b: a * b).astype(dtype)


def _oracle_quantize(x, tile):
    scale = compute_scale(x, tile)
    fmax = FMT_MAX[E4M3]
    if x.dtype == jnp.bfloat16:
        xf = _oracle_tiled_op(x, scale.astype(jnp.bfloat16), tile,
                              lambda a, b: a / b)
        data = jnp.clip(xf, jnp.bfloat16(-fmax), jnp.bfloat16(fmax))
    else:
        xf = _oracle_tiled_op(x.astype(jnp.float32), scale, tile,
                              lambda a, b: a / b)
        data = jnp.clip(xf, -fmax, fmax)
    return data.astype(E4M3), scale


def _shape(tile, n):
    """n tiles along every tiled axis, three elements along the rest."""
    return tuple(n * t if t > 1 else 3 for t in tile)


def _special_payload(tile, n, seed):
    """e4m3 values: the first tile all zero, +-448 and subnormals in the
    last one."""
    shape = _shape(tile, n)
    r = np.random.default_rng(seed)
    v = r.normal(size=shape) * np.exp2(r.integers(-9, 8, size=shape))
    v[tuple(slice(0, t) for t in tile)] = 0.0
    v.reshape(-1)[-8:-1] = [448.0, -448.0, 2.0 ** -9, -(2.0 ** -9),
                            2.0 ** -7, 3 * 2.0 ** -9, 0.0]
    return jnp.asarray(v, jnp.float32).astype(E4M3)


def _po2_scales(tile, n, seed):
    shape = tuple(m // t for m, t in zip(_shape(tile, n), tile))
    r = np.random.default_rng(seed + 1)
    s = np.exp2(r.integers(-20, 20, size=shape)).astype(np.float32)
    s.reshape(-1)[0] = 1.0                   # the all-zero tile's scale
    return jnp.asarray(s)


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("tile,n", CASES, ids=str)
def test_dequantize_bitwise_equal_to_split_form(tile, n, dtype):
    q = QTensor(_special_payload(tile, n, 0), _po2_scales(tile, n, 0), tile)
    got = jax.jit(_dequantize_nocount, static_argnums=1)(q, dtype)
    want = jax.jit(_oracle_dequantize, static_argnums=1)(q, dtype)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("in_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("tile,n", CASES, ids=str)
def test_quantize_bitwise_equal_to_split_form(tile, n, in_dtype):
    # dequantized specials times a spread of po2 scales: every value lands
    # on an e4m3 grid point, the last tile's amax gives back +-448 and its
    # subnormals
    q = QTensor(_special_payload(tile, n, 3), _po2_scales(tile, n, 3), tile)
    x = _dequantize_nocount(q, jnp.float32).astype(in_dtype)
    x = x.at[(-1,) * x.ndim].set(0.3)          # off the grid: rounds
    got = jax.jit(lambda a: quantize(a, tile))(x)
    want_data, want_scale = jax.jit(lambda a: _oracle_quantize(a, tile))(x)
    np.testing.assert_array_equal(_bits(got.scale), _bits(want_scale))
    np.testing.assert_array_equal(_bits(got.data), _bits(want_data))
    assert float(got.scale.reshape(-1)[0]) == 1.0          # the zero tile
    assert {448.0, -448.0} <= set(
        np.asarray(got.data.astype(jnp.float32)).reshape(-1)[-8:-1].tolist())


def _payload_reshapes(fn, q):
    """(in shape, out shape) of every reshape in fn's jaxpr whose operand
    has as many elements as the payload (the small scale array's own
    repeat along the last axis is not one)."""
    jaxpr = jax.make_jaxpr(fn)(q)
    out = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "reshape":
                shp = eqn.invars[0].aval.shape
                if int(np.prod(shp)) == q.data.size:
                    out.append((shp, eqn.outvars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return out


def _assert_no_last_axis_reshape(q):
    reshapes = _payload_reshapes(
        lambda t: _dequantize_nocount(t, jnp.bfloat16), q)
    assert reshapes, "the second-to-last axis is split: one reshape expected"
    for src, dst in reshapes:
        assert src[-1] == dst[-1], (src, dst)


def test_expert_weight_dequantize_keeps_last_axis():
    E, D, F = 4, 2 * TILE, 3 * TILE
    q = QTensor(jnp.zeros((E, D, F), E4M3),
                jnp.ones((E, D // TILE, F // TILE), jnp.float32),
                (1, TILE, TILE))
    _assert_no_last_axis_reshape(q)


def _w8_we13(L=2, E=4, D=2 * TILE, Fe=TILE):
    r = np.random.default_rng(7)
    we13 = jnp.asarray(r.normal(size=(L, E, D, 2, Fe)), jnp.bfloat16)
    return we13, quantize_params_for_serving({"layers": {"we13": we13}})[
        "layers"]["we13"]


def test_w8_we13_dequantize_keeps_last_axis():
    _, q = _w8_we13()
    layer = retile(QTensor(q.data[0], q.scale[0], q.tile), (1, TILE, TILE))
    _assert_no_last_axis_reshape(layer)
    _assert_no_last_axis_reshape(q)


def test_w8_we13_is_stored_merged_with_the_split_blocks():
    """Merging gate/up at load time gives the blocks and scales of
    quantizing (L, E, D, g, Fe) and merging after (Fe is whole tiles)."""
    we13, q = _w8_we13()
    L, E, D, g, Fe = we13.shape
    assert q.data.shape == (L, E, D, g * Fe)
    assert q.tile == (1, 1, TILE, TILE)
    split = quantize(we13, (1, 1, TILE, 1, TILE))
    np.testing.assert_array_equal(
        _bits(q.data), _bits(split.data.reshape(L, E, D, g * Fe)))
    np.testing.assert_array_equal(
        _bits(q.scale), _bits(split.scale.reshape(L, E, D // TILE, -1)))
