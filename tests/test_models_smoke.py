"""Per-architecture smoke tests: reduced config, one train step + decode.

Asserts output shapes, finiteness (no NaNs), and prefill/decode parity
(decoding token t+1 from a prefix must match the full-sequence forward).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, RunConfig, smoke_config
from repro.models import build_model

RUN = RunConfig(attn_impl="full", remat="none", lr_chunk=8, moe_group=64)
# parity/equivalence checks run in f32: they test correctness, not precision
RUN_F32 = RunConfig(
    attn_impl="full", remat="none", lr_chunk=8, moe_group=64,
    compute_dtype="float32", decode_cache_dtype="float32",
)
B, S = 2, 16


def _batch(cfg, key):
    kt, kf = jax.random.split(key)
    tokens = jax.random.randint(kt, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(kf, (B, S, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_loss(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg, RUN)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    # random init: loss ≈ ln(vocab_padded); generous sanity band
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3.0 * np.log(cfg.vocab_padded)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_grad_step_finite(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg, RUN)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    def loss_of(p):
        return model.loss_fn(p, batch)[0]

    grads = jax.jit(jax.grad(loss_of))(params)
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    # at least the embedding gradient must be nonzero
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_parity(arch):
    """decode_step(prefix) logits == full forward logits at that position."""
    from dataclasses import replace

    cfg = smoke_config(arch)
    if cfg.family == "moe":
        # capacity drops are routing-history dependent; parity needs none
        cfg = replace(cfg, capacity_factor=float(cfg.n_experts))
    model = build_model(cfg, RUN_F32)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    if cfg.is_encdec:
        frames = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        logits_a, cache = model.prefill(params, {"frames": frames, "tokens": tokens[:, :-1]},
                                        max_len=S + 4)
        logits_b, cache = model.decode_step(params, cache, tokens[:, -1])
        # oracle: prefill over the full sequence
        logits_full, _ = model.prefill(params, {"frames": frames, "tokens": tokens},
                                       max_len=S + 4)
    else:
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        logits_a, cache = model.prefill(params, tokens[:, :-1], max_len=S + 4)
        logits_b, cache = model.decode_step(params, cache, tokens[:, -1])
        logits_full, _ = model.prefill(params, tokens, max_len=S + 4)
    np.testing.assert_allclose(
        np.asarray(logits_b), np.asarray(logits_full), rtol=1e-3, atol=1e-4
    )
    assert int(cache["pos"]) == S


@pytest.mark.parametrize("arch", ["qwen25_3b", "zamba2_7b", "rwkv6_3b", "phi35_moe"])
def test_multi_token_decode(arch):
    """Greedy-decode 4 tokens; logits stay finite and cache advances."""
    cfg = smoke_config(arch)
    model = build_model(cfg, RUN)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, 8), 0, cfg.vocab_size)
    logits, cache = model.prefill(params, tokens, max_len=16)
    step = jax.jit(model.decode_step)
    for i in range(4):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32) % cfg.vocab_size
        logits, cache = step(params, cache, tok)
        assert np.all(np.isfinite(np.asarray(logits)))
    assert int(cache["pos"]) == 12


def test_scan_vs_unrolled_identical():
    """scan_layers=False (cost lowering) must be numerically identical."""
    cfg = smoke_config("qwen25_3b")
    from dataclasses import replace

    m_scan = build_model(cfg, RUN_F32)
    m_unroll = build_model(cfg, replace(RUN_F32, scan_layers=False))
    params = m_scan.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l1, _ = jax.jit(m_scan.loss_fn)(params, batch)
    l2, _ = jax.jit(m_unroll.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_chunked_attention_matches_full_in_model():
    from dataclasses import replace

    cfg = smoke_config("granite_20b")
    m_full = build_model(cfg, RUN)
    m_chunk = build_model(cfg, replace(RUN, attn_impl="chunked", q_chunk=8, kv_chunk=8))
    params = m_full.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l1, _ = jax.jit(m_full.loss_fn)(params, batch)
    l2, _ = jax.jit(m_chunk.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-3)


def test_moe_sort_matches_einsum_when_no_drops():
    """With generous capacity both dispatch impls route identically."""
    from dataclasses import replace

    cfg = smoke_config("phi35_moe")
    cfg = replace(cfg, capacity_factor=4.0)
    m_e = build_model(cfg, replace(RUN, moe_impl="einsum", moe_group=32))
    m_s = build_model(cfg, replace(RUN, moe_impl="sort", moe_group=32))
    params = m_e.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l1, _ = jax.jit(m_e.loss_fn)(params, batch)
    l2, _ = jax.jit(m_s.loss_fn)(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)


@pytest.mark.parametrize("arch", ["qwen25_3b", "whisper_base"])
def test_init_honours_param_dtype(arch):
    """Serving draws bf16 weights; training keeps its float32 default."""
    from dataclasses import replace

    cfg = smoke_config(arch)
    for dtype in ("bfloat16", "float32"):
        model = build_model(cfg, replace(RUN, param_dtype=dtype))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert {l.dtype for l in jax.tree.leaves(params)} == {jnp.dtype(dtype)}
