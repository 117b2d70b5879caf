"""Serving features: FP8 KV cache and W8-resident weights (§Perf cell 3)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core.quant import QTensor
from repro.core.recipes import get_recipe
from repro.models.lm import ParallelPlan, decode_step, init_cache, init_params
from repro.serve.w8 import quantize_params_for_serving
from tests.conftest import make_mesh11


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("qwen3_moe_235b").reduced()
    mesh = make_mesh11()
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    params = init_params(cfg, jax.random.key(0))
    return cfg, mesh, plan, params


def test_fp8_kv_cache_halves_bytes_and_decodes(setup):
    cfg, mesh, plan, params = setup
    recipe = get_recipe("fp8_flow")
    B = 2
    c_bf = init_cache(cfg, B, 64)
    c_f8 = init_cache(cfg, B, 64, fp8_kv=True)
    bytes_bf = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(c_bf))
    bytes_f8 = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(c_f8))
    assert bytes_f8 < 0.6 * bytes_bf
    with mesh:
        lg, _ = decode_step(cfg, recipe, plan, params, c_f8,
                            jnp.ones((B, 1), jnp.int32), jnp.int32(2))
    assert bool(jnp.isfinite(lg).all())


def test_w8_resident_weights_decode_matches_bf16_weights(setup):
    cfg, mesh, plan, params = setup
    recipe = get_recipe("fp8_flow")
    qparams = quantize_params_for_serving(params)
    # expert weights became QTensors; everything else untouched
    assert isinstance(qparams["layers"]["we13"], QTensor)
    assert isinstance(qparams["layers"]["we2"], QTensor)
    assert not isinstance(qparams["layers"]["wq"], QTensor)
    # payload bytes halved (+ small scale overhead)
    w_bf = params["layers"]["we13"]
    w_q8 = qparams["layers"]["we13"]
    assert (w_q8.data.size * 1 + w_q8.scale.size * 4) < 0.6 * w_bf.size * 2

    B = 2
    toks = jnp.ones((B, 1), jnp.int32)
    with mesh:
        lg_bf, _ = decode_step(cfg, recipe, plan, params,
                               init_cache(cfg, B, 64), toks, jnp.int32(1))
        lg_w8, _ = decode_step(cfg, recipe, plan, qparams,
                               init_cache(cfg, B, 64), toks, jnp.int32(1))
    a = np.asarray(lg_bf, np.float32).ravel()
    b = np.asarray(lg_w8, np.float32).ravel()
    cos = a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-30)
    # the training recipe quantizes the same weights per step, so W8-resident
    # decode is numerically near-identical to the on-the-fly path
    assert cos > 0.999, cos


def test_w8_we13_specs_follow_the_merged_layout(setup):
    """The W8 `we13` is stored (L, E, D, g*Fe): its payload and scales
    shard on the expert axis, like the bf16 (L, E, D, g, Fe) leaf."""
    from repro.launch import sharding
    cfg, mesh, _, params = setup
    shapes = jax.eval_shape(quantize_params_for_serving, params)
    we13 = shapes["layers"]["we13"]
    L, E, D, g, Fe = params["layers"]["we13"].shape
    assert we13.data.shape == (L, E, D, g * Fe)
    specs = sharding.tree_specs(cfg, mesh, shapes)["layers"]
    bf16 = sharding.tree_specs(cfg, mesh, params)["layers"]["we13"].spec
    assert tuple(bf16)[:2] == (None, "model")
    for leaf in (specs["we13"].data, specs["we13"].scale):
        assert tuple(leaf.spec) == (None, "model", None, None)
