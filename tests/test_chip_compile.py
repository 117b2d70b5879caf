"""Compiles for a described TPU v5e chip, and chip_smoke.py's phases at a
tiny size on the CPU.

The kernels are compiled (interpret=False) at DeepSeek-V2-Lite widths --
d_model 2048, expert width 1408 -- so a block layout the chip's compiler
refuses fails here, with no chip.  The topology is described inside a
fixture only: one process at a time may load the TPU library, and under
pytest-xdist only the worker that runs this file does.
"""
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.kernels import (fp8_transpose, fused_permute_pad,  # noqa: E402
                           fused_swiglu_quant, grouped_gemm_fp8,
                           grouped_gemm_nt_fp8, quantize)

D, F, E, C, M = 2048, 1408, 8, 256, 1024
E4, F32, BF16, I32 = jnp.float8_e4m3fn, jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _gg(quant_out=False, masked=False):
    f = (grouped_gemm_fp8.masked_grouped_gemm_fp8_pallas if masked
         else grouped_gemm_fp8.grouped_gemm_fp8_pallas)
    return functools.partial(f, quant_out=quant_out, interpret=False)


_MASK = ((E,), I32)
_GG1 = [((E, C, D), E4), ((E, C, D // 128), F32),
        ((E, D, 2 * F), E4), ((E, D // 128, 2 * F // 128), F32)]
_GG2 = [((E, C, F), E4), ((E, C, F // 128), F32),
        ((E, F, D), E4), ((E, F // 128, D // 128), F32)]
_NT = [((E, D, C), E4), ((E, D, C // 128), F32),
       ((E, 2 * F, C), E4), ((E, 2 * F, C // 128), F32)]
KERNELS = {
    "quantize_rowwise": (functools.partial(
        quantize.quantize_rowwise_pallas, interpret=False), [((M, D), BF16)]),
    "fused_swiglu_quant": (functools.partial(
        fused_swiglu_quant.fused_swiglu_quant_pallas, interpret=False),
        [((M, 2 * F), BF16)]),
    "fp8_transpose": (functools.partial(
        fp8_transpose.fp8_transpose_pallas, interpret=False),
        [((M, D), E4), ((M, D // 128), F32)]),
    "fused_permute_pad": (lambda x, s, r: fused_permute_pad
                          .fused_permute_pad_pallas(x, s, r, r.shape[0],
                                                    interpret=False),
                          [((M, D), E4), ((M, D // 128), F32),
                           ((M + M // 2,), I32)]),
    "grouped_gemm_fp8": (_gg(), _GG1),
    "grouped_gemm_fp8_quant_out": (_gg(quant_out=True), _GG2),
    "grouped_gemm_fp8_masked": (_gg(masked=True), _GG1 + [_MASK]),
    "grouped_gemm_fp8_masked_quant_out": (_gg(quant_out=True, masked=True),
                                          _GG2 + [_MASK]),
    "grouped_gemm_swiglu_quant_masked": (functools.partial(
        grouped_gemm_fp8.masked_grouped_gemm_swiglu_quant_pallas,
        interpret=False), _GG1 + [_MASK]),
    "grouped_gemm_nt_fp8": (functools.partial(
        grouped_gemm_nt_fp8.grouped_gemm_nt_fp8_pallas, interpret=False),
        _NT),
    "grouped_gemm_nt_fp8_masked": (functools.partial(
        grouped_gemm_nt_fp8.masked_grouped_gemm_nt_fp8_pallas,
        interpret=False), _NT + [_MASK]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text(), name


def test_pallas_train_step_compiles_for_v5e(topo, monkeypatch):
    """The fp8_flow step with Recipe.use_pallas compiles for the chip: the
    kernels lower inside the MoE shard_map, under remat.  The CPU backend
    would interpret them, so the test steers the kernels to compile."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    import repro.kernels
    from repro.core.recipes import get_recipe
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch import sharding, train
    from repro.optim import adamw
    from repro.train.train_step import init_train_state, make_train_step
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(repro.kernels.ops, "interpret_mode", lambda: False)

    cfg = dataclasses.replace(get_arch("deepseek_v2_lite").reduced(),
                              n_layers=2)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan = sharding.make_plan(cfg, mesh)
    opt = adamw.AdamWConfig()
    shapes = jax.eval_shape(lambda k: init_train_state(cfg, opt, k),
                            jax.random.key(0))
    batch = jax.eval_shape(lambda: make_batch(
        DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=2), 0))
    step = make_train_step(cfg, get_recipe("fp8_flow", use_pallas=True),
                           plan, opt, total_steps=2, warmup_steps=1)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(
            train.state_shardings(cfg, mesh, shapes, None),
            sharding.batch_specs(mesh, batch, plan.dp_axes)),
            donate_argnums=(0,)).lower(shapes, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# chip_smoke.py's phases on the CPU at a tiny size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_cfg():
    return dataclasses.replace(get_arch("deepseek_v2_lite").reduced(),
                               n_layers=2)


def test_smoke_kernels_phase_cpu():
    diffs = chip_smoke.phase_kernels(D=256, F=128, E=2, C=256)
    assert set(diffs) >= {"quantize_rowwise", "fp8_transpose",
                          "grouped_gemm_swiglu_quant_masked"}
    assert not any(diffs.values())


def test_smoke_train_phase_cpu(tiny_cfg):
    # the Pallas path needs the compiled kernels (the interpreter cannot run
    # under remat): test_pallas_train_step_compiles_for_v5e covers it
    losses = chip_smoke.phase_train(tiny_cfg, steps=3, seq_len=64, batch=2,
                                    paths=("xla",))
    assert len(losses["xla"]) == 3


def test_smoke_serve_phase_cpu(tiny_cfg):
    out = chip_smoke.phase_serve(tiny_cfg, n_requests=3, prompt_len=(20, 40),
                                 max_new=4, buckets=(32, 64), page_size=8)
    assert out["finished"] == 3 and out["tokens"] == 12


def test_smoke_ep_phase_cpu(tiny_cfg):
    # every device of this process on the model axis (one on the CPU)
    worst = chip_smoke.phase_ep(tiny_cfg, steps=2, seq_len=64, batch=2,
                                model=jax.device_count())
    assert worst["loss"] <= chip_smoke.EP_LOSS_RTOL


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to JAX untouched;
    otherwise the cache is the checkout's fixed .jax_cache/."""
    from repro.launch import compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(compile_cache.CHECKOUT_CACHE) == os.path.join(root,
                                                            ".jax_cache")
    want = str(tmp_path) if from_env else str(compile_cache.CHECKOUT_CACHE)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert got == want
    assert after == (before if from_env else want)


def test_smoke_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
