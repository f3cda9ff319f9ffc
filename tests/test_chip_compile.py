"""AOT compiles for a described TPU v5e: the serving path at qwen2.5-3b width.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes Mosaic cannot tile, programs that do not fit its HBM).
Interpret-mode tests cannot see either.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.compile_cache import TPU_LOG_DEFAULT
from repro.configs import get_config
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.launch.serve import SERVE_RUN
from repro.models import build_model

#: usable HBM of one v5e chip as its compiler reports it (15.75 GiB)
V5E_HBM_BYTES = int(15.75 * 2**30)
B, PROMPT, PAGE, TABLE = 4, 128, 16, 10  # the serve smoke's shapes
N_PAGES = 1 + B * TABLE


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # libtpu loads here and logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", TPU_LOG_DEFAULT))
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def qwen(one_chip):
    """The full-width model with bf16 weight shapes placed on one chip."""
    model = build_model(get_config("qwen2.5-3b"), SERVE_RUN)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, _on(params, one_chip)


def _on(tree, sharding):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding), tree
    )


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


#: (configuration, rows, table width): the serve smoke's, and the benchmark's
#: chat (32 slots of 161 pages) and batch-long (3 slots of 113) cells
SMOKE = ("qwen2.5-3b", B, TABLE)


@pytest.mark.parametrize("dtype,shape", [
    pytest.param(jnp.bfloat16, SMOKE, id="bfloat16"),
    pytest.param(jnp.float32, SMOKE, id="float32"),
    pytest.param(jnp.bfloat16, ("phi3-mini", B, TABLE), id="bfloat16-phi3-mini"),
    pytest.param(jnp.bfloat16, ("qwen2.5-3b", 32, 161), id="bfloat16-chat"),
    pytest.param(jnp.bfloat16, ("phi3-mini", 3, 113), id="bfloat16-batch-long"),
])
@pytest.mark.parametrize("bk", [None, 8])
def test_paged_kernel_compiles_at_qwen_widths(one_chip, dtype, shape, bk):
    arch, rows, table = shape
    cfg = get_config(arch)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    n_pages = 1 + rows * table
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda q, k, v, t, n: paged_decode_attention_pallas(
            q, k, v, t, n, bk=bk, interpret=False)
    ).lower(
        sds((rows, hq, d), dtype), sds((hkv, n_pages, PAGE, d), dtype),
        sds((hkv, n_pages, PAGE, d), dtype), sds((rows, table), jnp.int32),
        sds((rows,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_prefill_fits_one_chip(one_chip, qwen):
    model, params = qwen
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(params))
    tokens = jax.ShapeDtypeStruct((B, PROMPT), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: model.prefill(p, t, max_len=PROMPT)).lower(
        params, tokens).compile()
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


def test_full_width_paged_decode_step_fits_and_holds_kernel(one_chip, qwen, monkeypatch):
    import repro.kernels.paged_attention.ops as paged_ops

    # the described chip is not the default backend, which would pick the
    # interpreter: lower the kernel as the chip itself does
    monkeypatch.setattr(paged_ops, "interpret_default", lambda: False)
    model, params = qwen
    cache = _on(jax.eval_shape(lambda: model.init_paged_cache(N_PAGES, PAGE)), one_chip)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    live = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(model.decode_step_paged).lower(
        params, cache, i32((B,)), i32((B, TABLE)), i32((B,)), live).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode-32-slots", "prefill-512"])
def test_held_expert_gmm_compiles_at_phimoe_widths(one_chip, rows, monkeypatch):
    """Phi-3.5-MoE's held experts (8 of 16, d 4096, ff 6400): the SwiGLU's three
    grouped matmuls at a decode step's 32 x 2 routed rows and a 512-token prefill's."""
    import repro.kernels.moe_gmm.ops as gmm_ops

    monkeypatch.setattr(gmm_ops, "interpret_default", lambda: False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    up = sds((8, 4096, 6400), jnp.bfloat16)
    compiled = jax.jit(
        lambda x, wi, wg, wo, sizes: gmm_ops.held_expert_ffn(x, wi, wg, wo, sizes, 0)
    ).lower(sds((rows, 4096), jnp.bfloat16), up, up, sds((8, 6400, 4096), jnp.bfloat16),
            sds((16,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phimoe_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """phi3.5-moe as ``bench/configs/phi3.5-moe.json`` cuts it (8 layers, experts
    0-7 of 16) with the batch-decode mix's pool, 32 slots of 97 pages: the decode
    step holds both kernels and fits one chip beside its 11.27 GB of weights."""
    from dataclasses import replace

    import repro.kernels.moe_gmm.ops as gmm_ops
    import repro.kernels.paged_attention.ops as paged_ops

    monkeypatch.setattr(paged_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(gmm_ops, "interpret_default", lambda: False)
    cfg = replace(get_config("phi3.5-moe-42b-a6.6b"), n_layers=8, experts_held=8)
    model = build_model(cfg, SERVE_RUN)
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    rows, table = 32, 97
    cache = _on(jax.eval_shape(lambda: model.init_paged_cache(1 + rows * table, PAGE)), one_chip)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    live = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
        params, cache, i32((rows,)), i32((rows, table)), i32((rows,)), live).compile()
    text = compiled.as_text()
    assert "held_expert_gmm" in text and "paged_decode_attention" in text
    assert _hbm_bytes(compiled) <= V5E_HBM_BYTES
