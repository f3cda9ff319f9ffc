"""Unified decoder-only LM covering the dense / moe / ssm / hybrid families.

One model class, four layer families:

* dense   — GQA attention + SwiGLU MLP (qwen, phi3, granite, chameleon)
* moe     — GQA attention + top-k routed experts (phi3.5-moe, grok-1)
* ssm     — RWKV-6 layers (attention-free)
* hybrid  — Mamba-2 groups + one **shared** attention block applied after
            every `attn_every` SSM layers (zamba2)

Layers are stacked (leading L dim) and traversed with `lax.scan`
(`RunConfig.scan_layers=False` unrolls — used by the cost-exact dry-run
lowering).  `RunConfig.remat="layer"` wraps the layer body in
`jax.checkpoint` (required memory policy at the assigned shapes).

API: ``init``, ``loss_fn`` (train), ``prefill`` + ``decode_step`` (serve).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, RunConfig

from . import rwkv as rwkv_mod
from . import ssm as ssm_mod
from .layers import apply_rope, attention, full_attention, layernorm, mlp_swiglu, rmsnorm
from .moe import moe_dropless, moe_layer
from .params import cast_tree, dense_init, embed_init, stack_layers


def _dt(run: RunConfig):
    return jnp.dtype(run.compute_dtype)


def _norm(x, p, name: str, cfg: ArchConfig):
    """RMSNorm by ``p[name]``, or LayerNorm by it and ``p[name + "_b"]``."""
    if cfg.norm == "layer":
        return layernorm(x, p[name], p[name + "_b"], cfg.norm_eps)
    return rmsnorm(x, p[name], cfg.norm_eps)


def _norm_init(cfg: ArchConfig, *names: str) -> dict:
    p = {n: jnp.ones((cfg.d_model,), jnp.float32) for n in names}
    if cfg.norm == "layer":
        p |= {n + "_b": jnp.zeros((cfg.d_model,), jnp.float32) for n in names}
    return p


# ---------------------------------------------------------------------------
# attention + mlp blocks (shared by dense/moe/hybrid/encdec)
# ---------------------------------------------------------------------------
def init_attn(key, cfg: ArchConfig):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd),
        "wk": dense_init(ks[1], d, hkv * hd),
        "wv": dense_init(ks[2], d, hkv * hd),
        "wo": dense_init(ks[3], hq * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((d,), jnp.float32)
    return p


def _out_proj(o, p):
    """Attention output (..., Hq * Dh) -> (..., d), plus the o bias where there is one."""
    y = jnp.einsum("...e,ed->...d", o, p["wo"].astype(o.dtype))
    return y + p["bo"].astype(o.dtype) if "bo" in p else y


def _qkv(x, p, cfg: ArchConfig, positions, rope: bool = True):
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = x.dtype
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt))
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(x, p, cfg: ArchConfig, run: RunConfig, positions, causal=True, rope=True):
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    from . import sharding_ctx as sc

    b, s, _ = x.shape
    q, k, v = _qkv(x, p, cfg, positions, rope)
    if run.constrain_activations:
        dp = sc.dp_axes()
        q = sc.constrain(q, dp, None, "model", None)
        k = sc.constrain(k, dp, None, "model", None)
        v = sc.constrain(v, dp, None, "model", None)
    if run.attn_impl == "full" or s % run.q_chunk or s % run.kv_chunk:
        o = full_attention(q, k, v, causal=causal)
    else:
        o = attention(
            q, k, v, impl="chunked", causal=causal,
            q_chunk=run.q_chunk, kv_chunk=run.kv_chunk,
            unroll=run.scan_unroll, skip_masked_blocks=run.skip_masked_blocks,
        )
    return _out_proj(o.reshape(b, s, -1).astype(x.dtype), p), (k, v)


def attn_block_decode_paged(
    x, p, cfg: ArchConfig, run: RunConfig, k_pages, v_pages, page_table, kv_len, live
):
    """Single-token attention against a paged KV pool.

    x: (B, d); pages: (Hkv, P, ps, Dh); page_table: (B, max_pages) int32;
    kv_len: (B,) tokens already cached per row; live: (B,) bool.  Each live
    row writes its new K/V at position ``kv_len[b]`` inside the page the
    table maps it to; dead rows (free slots) write to the reserved null
    page and attend over an empty cache — their output is exact zeros.
    Returns (out (B, d), new_k_pages, new_v_pages).
    """
    from repro.kernels.paged_attention import NULL_PAGE, paged_decode_attention

    b, _ = x.shape
    ps = k_pages.shape[2]
    q, k, v = _qkv(x[:, None], p, cfg, kv_len[:, None], rope=True)
    cdt = k_pages.dtype
    page = jnp.where(live, page_table[jnp.arange(b), kv_len // ps], NULL_PAGE)
    off = kv_len % ps
    # (B, Hkv, Dh) rows -> the (Hkv, B, Dh) slice `[:, page, off]` selects
    k_pages = k_pages.at[:, page, off].set(k[:, 0].swapaxes(0, 1).astype(cdt))
    v_pages = v_pages.at[:, page, off].set(v[:, 0].swapaxes(0, 1).astype(cdt))
    new_len = jnp.where(live, kv_len + 1, 0)
    o = paged_decode_attention(q[:, 0], k_pages, v_pages, page_table, new_len)
    return _out_proj(o.reshape(b, -1).astype(x.dtype), p), k_pages, v_pages


def attn_block_decode(x, p, cfg: ArchConfig, run: RunConfig, k_cache, v_cache, pos):
    """Single-token attention against a cache.

    x: (B, d); k/v_cache: (B, Smax, Hkv, Dh); pos: scalar current length.
    Returns (out (B, d), new_k, new_v).
    """
    b, d = x.shape
    q, k, v = _qkv(x[:, None], p, cfg, jnp.full((b, 1), pos), rope=True)
    cdt = k_cache.dtype
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(cdt), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(cdt), pos, axis=1)
    o = full_attention(
        q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
        causal=False, kv_len=jnp.full((b,), pos + 1),
    )
    return _out_proj(o.reshape(b, -1).astype(x.dtype), p), k_cache, v_cache


def init_mlp(key, cfg: ArchConfig):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], d, ff),
        "wg": dense_init(ks[1], d, ff),
        "wo2": dense_init(ks[2], ff, d),
    }


def init_moe(key, cfg: ArchConfig):
    """The router over every expert; the weights of the experts held here."""
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_held
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, cfg.n_experts),
        "wi": jax.vmap(lambda k: dense_init(k, d, ff))(jax.random.split(ks[1], e)),
        "wg": jax.vmap(lambda k: dense_init(k, d, ff))(jax.random.split(ks[2], e)),
        "wo": jax.vmap(lambda k: dense_init(k, ff, d))(jax.random.split(ks[3], e)),
    }


# ---------------------------------------------------------------------------
# layer families
# ---------------------------------------------------------------------------
def init_layer(key, cfg: ArchConfig):
    if cfg.family in ("dense", "moe"):
        k1, k2 = jax.random.split(key)
        p = _norm_init(cfg, "ln1", "ln2") | {"attn": init_attn(k1, cfg)}
        p["moe" if cfg.family == "moe" else "mlp"] = (
            init_moe(k2, cfg) if cfg.family == "moe" else init_mlp(k2, cfg)
        )
        return p
    if cfg.family == "ssm":
        return rwkv_mod.init_rwkv_layer(key, cfg.d_model, cfg.d_ff)
    if cfg.family == "hybrid":
        return {
            "ln": jnp.ones((cfg.d_model,), jnp.float32),
            "ssm": ssm_mod.init_ssm_block(key, cfg.d_model, cfg.ssm_state),
        }
    raise ValueError(cfg.family)


def _moe_serve(h, p, cfg: ArchConfig, live=None):
    """Serving's dropless expert layer over (..., d) tokens; ``live`` (T,) or None."""
    y = moe_dropless(h.reshape(-1, h.shape[-1]), p, cfg.n_experts, cfg.experts_per_token,
                     cfg.expert_first, cfg.router, cfg.router_jitter, live)
    return y.reshape(h.shape)


def apply_layer(x, p, cfg: ArchConfig, run: RunConfig, positions, dropless: bool = False):
    """Train/prefill layer body. Returns (x, (aux_loss, cache)).

    ``dropless``: a moe layer serves every token (prefill) rather than
    dispatching by capacity (training).
    """
    from . import sharding_ctx as sc

    if cfg.family in ("dense", "moe"):
        a, (k, v) = attn_block(_norm(x, p, "ln1", cfg), p["attn"], cfg, run, positions)
        x = x + a
        if run.constrain_activations:
            x = sc.constrain(x, sc.dp_axes(), None, None)
        h = _norm(x, p, "ln2", cfg)
        if cfg.family == "moe" and dropless:
            m, aux = _moe_serve(h, p["moe"], cfg), 0.0
        elif cfg.family == "moe":
            if cfg.n_held != cfg.n_experts:
                raise ValueError("capacity dispatch needs every expert held")
            m, aux = moe_layer(
                h, p["moe"], cfg.n_experts, cfg.experts_per_token,
                cfg.capacity_factor, impl=run.moe_impl, group_size=run.moe_group,
                router=cfg.router, eps=cfg.router_jitter,
            )
        else:
            m = mlp_swiglu(h, p["mlp"]["wi"], p["mlp"]["wg"], p["mlp"]["wo2"],
                           constrain=run.constrain_activations)
            aux = 0.0
        x = x + m
        if run.constrain_activations:
            x = sc.constrain(x, sc.dp_axes(), None, None)
        cdt = jnp.dtype(run.decode_cache_dtype)
        return x, (jnp.asarray(aux, jnp.float32), {"k": k.astype(cdt), "v": v.astype(cdt)})
    if cfg.family == "ssm":
        y, cache = rwkv_mod.rwkv_layer(x, p, chunk=run.lr_chunk, eps=cfg.norm_eps,
                                       unroll=run.scan_unroll)
        return y, (jnp.asarray(0.0, jnp.float32), cache)
    if cfg.family == "hybrid":
        y, cache = ssm_mod.ssm_block(
            rmsnorm(x, p["ln"], cfg.norm_eps), p["ssm"], cfg.ssm_state,
            chunk=run.lr_chunk, unroll=run.scan_unroll,
        )
        return x + y, (jnp.asarray(0.0, jnp.float32), cache)
    raise ValueError(cfg.family)


def _decode_tail(x, a, p, cfg: ArchConfig, run: RunConfig, live=None):
    """Dense/moe decode-layer tail: attn residual + norm + mlp/moe residual.
    ``live`` (B,): rows that route to experts (all where None)."""
    x = x + a
    h = _norm(x, p, "ln2", cfg)
    if cfg.family == "moe":
        m = _moe_serve(h, p["moe"], cfg, live)
    else:
        m = mlp_swiglu(h[:, None], p["mlp"]["wi"], p["mlp"]["wg"], p["mlp"]["wo2"])[:, 0]
    return x + m


def apply_layer_decode(x, p, cache, cfg: ArchConfig, run: RunConfig, pos):
    """Single-token layer body. Returns (x, new_cache)."""
    if cfg.family in ("dense", "moe"):
        a, k, v = attn_block_decode(
            _norm(x, p, "ln1", cfg), p["attn"], cfg, run,
            cache["k"], cache["v"], pos,
        )
        return _decode_tail(x, a, p, cfg, run), {"k": k, "v": v}
    if cfg.family == "ssm":
        return rwkv_mod.rwkv_layer_decode(x, p, cache, eps=cfg.norm_eps)
    if cfg.family == "hybrid":
        y, new_cache = ssm_mod.ssm_block_decode(
            rmsnorm(x, p["ln"], cfg.norm_eps), p["ssm"], cache, cfg.ssm_state
        )
        return x + y, new_cache
    raise ValueError(cfg.family)


def apply_layer_decode_paged(
    x, p, cache, cfg: ArchConfig, run: RunConfig, page_table, kv_len, live
):
    """Paged single-token layer body (dense/moe only). Returns (x, new_cache)."""
    a, k_pages, v_pages = attn_block_decode_paged(
        _norm(x, p, "ln1", cfg), p["attn"], cfg, run,
        cache["k"], cache["v"], page_table, kv_len, live,
    )
    return _decode_tail(x, a, p, cfg, run, live), {"k": k_pages, "v": v_pages}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclass
class DecoderLM:
    cfg: ArchConfig
    run: RunConfig = RunConfig()

    # ----------------------------------------------------------- init
    def init(self, key) -> dict:
        """Random parameters in ``run.param_dtype``.

        Leaves are drawn in float32 and cast; call under `jax.jit` so a
        narrower dtype never holds the float32 tree on the device.
        """
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        params = {"embed": embed_init(ks[0], cfg.vocab_padded, cfg.d_model)}
        params |= _norm_init(cfg, "final_norm")
        if not cfg.tie_embeddings:
            params["head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_padded)
        if cfg.head_bias:
            params["head_b"] = jnp.zeros((cfg.vocab_padded,), jnp.float32)
        if cfg.family == "hybrid":
            g, gsz, tail = self._hybrid_layout()
            params["groups"] = stack_layers(
                lambda k: stack_layers(lambda k2: init_layer(k2, cfg), k, gsz), ks[2], g
            )
            if tail:
                params["tail"] = stack_layers(lambda k: init_layer(k, cfg), ks[3], tail)
            params["shared"] = {
                "ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "attn": init_attn(ks[4], cfg),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                "mlp": init_mlp(ks[5], cfg),
            }
        else:
            params["layers"] = stack_layers(lambda k: init_layer(k, cfg), ks[2], cfg.n_layers)
        return cast_tree(params, self.run.param_dtype)

    def _hybrid_layout(self):
        g = self.cfg.n_layers // self.cfg.attn_every
        return g, self.cfg.attn_every, self.cfg.n_layers % self.cfg.attn_every

    # ----------------------------------------------------------- forward
    def _embed(self, params, tokens, dtype):
        return params["embed"].astype(dtype)[tokens]

    def _logits(self, params, x):
        head = params.get("head")
        w = (head if head is not None else params["embed"].T).astype(x.dtype)
        y = jnp.einsum("...d,dv->...v", x, w)
        return y + params["head_b"].astype(x.dtype) if "head_b" in params else y

    def _layer_scan(self, params, x, positions, dropless: bool = False):
        """Run all layers; returns (x, aux_sum, cache_pytree)."""
        cfg, run = self.cfg, self.run

        def body(carry, p_l):
            h, aux = carry
            h2, (a, cache) = apply_layer(h, p_l, cfg, run, positions, dropless)
            return (h2, aux + a), cache

        body_fn = jax.checkpoint(body) if run.remat == "layer" else body

        def run_stack(x, stacked, length):
            if run.scan_layers:
                (x, aux), caches = jax.lax.scan(
                    body_fn, (x, jnp.float32(0.0)), stacked, length=length
                )
                return x, aux, caches
            aux = jnp.float32(0.0)
            caches = []
            for i in range(length):
                p_l = jax.tree.map(lambda a: a[i], stacked)
                (x, aux), cache = body_fn((x, aux), p_l)
                caches.append(cache)
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
            return x, aux, caches

        if cfg.family != "hybrid":
            return run_stack(x, params["layers"], cfg.n_layers)

        # hybrid: groups of SSM layers, shared attention block between groups
        g, gsz, tail = self._hybrid_layout()
        shared = params["shared"]

        def group_body(carry, p_group):
            h, aux = carry
            h, aux_g, ssm_caches = run_stack(h, p_group, gsz)
            a, (k, v) = attn_block(
                rmsnorm(h, shared["ln1"], cfg.norm_eps), shared["attn"], cfg, run, positions
            )
            h = h + a
            m = mlp_swiglu(
                rmsnorm(h, shared["ln2"], cfg.norm_eps),
                shared["mlp"]["wi"], shared["mlp"]["wg"], shared["mlp"]["wo2"],
            )
            cdt = jnp.dtype(run.decode_cache_dtype)
            return (h + m, aux + aux_g), (ssm_caches, {"k": k.astype(cdt), "v": v.astype(cdt)})

        if run.scan_layers:
            (x, aux), (ssm_caches, attn_caches) = jax.lax.scan(
                group_body, (x, jnp.float32(0.0)), params["groups"]
            )
        else:
            aux = jnp.float32(0.0)
            accs = []
            for i in range(g):
                p_g = jax.tree.map(lambda a: a[i], params["groups"])
                (x, aux), acc = group_body((x, aux), p_g)
                accs.append(acc)
            ssm_caches, attn_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *accs)
        cache = {"groups": ssm_caches, "shared_attn": attn_caches}
        if tail:
            x, aux_t, tail_caches = run_stack(x, params["tail"], tail)
            aux = aux + aux_t
            cache["tail"] = tail_caches
        return x, aux, cache

    # ----------------------------------------------------------- train
    def loss_fn(self, params, batch):
        """batch['tokens']: (B, S+1) int32. Returns (loss, metrics)."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, s = inputs.shape
        if cfg.frontend == "vlm" and "frame_embeddings" in batch:
            x = batch["frame_embeddings"].astype(dtype)  # stub frontend path
        else:
            x = self._embed(params, inputs, dtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, aux, _ = self._layer_scan(params, x, positions)
        x = _norm(x, params, "final_norm", cfg)
        loss = self._ce(params, x, targets)
        if cfg.family == "moe":
            loss = loss + 0.01 * aux / cfg.n_layers
        return loss, {"ce": loss, "aux": aux}

    def _ce(self, params, x, targets):
        run = self.run
        v = self.cfg.vocab_padded

        def ce_of(xc, tc):
            logits = self._logits(params, xc).astype(jnp.float32)
            lz = jax.nn.logsumexp(logits, axis=-1)
            if run.ce_impl == "onehot":
                # vocab-sharding-friendly gold pick: a fused masked reduce
                # over the local vocab shard + tiny all-reduce, instead of
                # a gather across the sharded vocab dimension (§Perf)
                iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
                gold = jnp.where(iota == tc[..., None], logits, 0.0).sum(axis=-1)
            else:
                gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
            return (lz - gold).sum(), tc.size

        if run.ce_chunk and x.shape[1] % run.ce_chunk == 0:
            n = x.shape[1] // run.ce_chunk
            xc = x.reshape(x.shape[0], n, run.ce_chunk, -1).transpose(1, 0, 2, 3)
            tc = targets.reshape(targets.shape[0], n, run.ce_chunk).transpose(1, 0, 2)

            def body(tot, xs):
                l, c = ce_of(*xs)
                return tot + l, None

            total, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, tc))
            return total / targets.size
        l, c = ce_of(x, targets)
        return l / c

    # ----------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int):
        """Allocate the decode cache (used via eval_shape in the dry-run)."""
        cfg, run = self.cfg, self.run
        cdt = jnp.dtype(run.decode_cache_dtype)
        hkv, hd = cfg.n_kv_heads, cfg.head_dim_

        def kv(b, s):
            return {
                "k": jnp.zeros((b, s, hkv, hd), cdt),
                "v": jnp.zeros((b, s, hkv, hd), cdt),
            }

        if cfg.family in ("dense", "moe"):
            caches = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(),
                kv(batch, max_len),
            )
            return {"layers": caches, "pos": jnp.int32(0)}
        if cfg.family == "ssm":
            c = rwkv_mod.init_rwkv_cache(batch, cfg.d_model)
            return {
                "layers": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), c
                ),
                "pos": jnp.int32(0),
            }
        if cfg.family == "hybrid":
            g, gsz, tail = self._hybrid_layout()
            ssm_c = ssm_mod.init_ssm_cache(batch, cfg.d_model, cfg.ssm_state)
            out = {
                "groups": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (g, gsz) + x.shape).copy(), ssm_c
                ),
                "shared_attn": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (g,) + x.shape).copy(), kv(batch, max_len)
                ),
                "pos": jnp.int32(0),
            }
            if tail:
                out["tail"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (tail,) + x.shape).copy(), ssm_c
                )
            return out
        raise ValueError(cfg.family)

    def init_paged_cache(self, n_pages: int, page_size: int):
        """Allocate the paged decode cache: per-layer K/V page pools.

        Returns ``{"layers": {"k": (L, Hkv, P, ps, Dh), "v": ...}}`` — no
        ``pos`` clock: position is per-row ragged ``kv_len``, owned by the
        host-side `repro.kernels.paged_attention.PagedKVPool`.  Dense/moe
        families only (ssm/hybrid keep recurrent state, nothing to page).
        """
        cfg, run = self.cfg, self.run
        if cfg.family not in ("dense", "moe"):
            raise ValueError(f"paged KV cache needs attention layers, not {cfg.family!r}")
        cdt = jnp.dtype(run.decode_cache_dtype)
        pool = jnp.zeros((cfg.n_layers, cfg.n_kv_heads, n_pages, page_size, cfg.head_dim_), cdt)
        return {"layers": {"k": pool, "v": pool.copy()}}

    def prefill(self, params, tokens, max_len: int | None = None):
        """tokens: (B, S). Returns (last-token logits (B, V), cache)."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        b, s = tokens.shape
        max_len = max_len or s
        x = self._embed(params, tokens, dtype)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, _, caches = self._layer_scan(params, x, positions, dropless=True)
        x = _norm(x, params, "final_norm", cfg)
        logits = self._logits(params, x[:, -1]).astype(jnp.float32)
        cache = self._package_cache(caches, b, s, max_len)
        return logits, cache

    def _package_cache(self, caches, b, s, max_len):
        cfg = self.cfg

        def pad_kv(x):  # (L, B, S, H, D) -> (L, B, max_len, H, D)
            if x.shape[2] == max_len:
                return x
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, max_len - x.shape[2])
            return jnp.pad(x, pad)

        if cfg.family in ("dense", "moe"):
            return {"layers": jax.tree.map(pad_kv, caches), "pos": jnp.int32(s)}
        if cfg.family == "ssm":
            return {"layers": caches, "pos": jnp.int32(s)}
        if cfg.family == "hybrid":
            out = dict(caches)
            out["shared_attn"] = jax.tree.map(pad_kv, caches["shared_attn"])
            out["pos"] = jnp.int32(s)
            return out
        raise ValueError(cfg.family)

    def decode_step(self, params, cache, token):
        """token: (B,) int32. Returns (logits (B, V), new cache)."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        x = self._embed(params, token, dtype)
        pos = cache["pos"]

        def stack_step(x, stacked_p, stacked_c, length):
            def body(h, xs):
                p_l, c_l = xs
                h, c_new = apply_layer_decode(h, p_l, c_l, cfg, run, pos)
                return h, c_new

            if run.scan_layers:
                return jax.lax.scan(body, x, (stacked_p, stacked_c), length=length)
            news = []
            for i in range(length):
                p_l = jax.tree.map(lambda a: a[i], stacked_p)
                c_l = jax.tree.map(lambda a: a[i], stacked_c)
                x, c_new = body(x, (p_l, c_l))
                news.append(c_new)
            return x, jax.tree.map(lambda *xs: jnp.stack(xs), *news)

        new_cache = {"pos": pos + 1}
        if cfg.family != "hybrid":
            x, caches = stack_step(x, params["layers"], cache["layers"], cfg.n_layers)
            new_cache["layers"] = caches
        else:
            g, gsz, tail = self._hybrid_layout()
            shared = params["shared"]

            def group_body(h, xs):
                p_g, ssm_c, attn_c = xs
                h, ssm_new = stack_step(h, p_g, ssm_c, gsz)
                a, k_new, v_new = attn_block_decode(
                    rmsnorm(h, shared["ln1"], cfg.norm_eps), shared["attn"], cfg, run,
                    attn_c["k"], attn_c["v"], pos,
                )
                h = h + a
                m = mlp_swiglu(
                    rmsnorm(h, shared["ln2"], cfg.norm_eps)[:, None],
                    shared["mlp"]["wi"], shared["mlp"]["wg"], shared["mlp"]["wo2"],
                )[:, 0]
                return h + m, (ssm_new, {"k": k_new, "v": v_new})

            if run.scan_layers:
                x, (ssm_caches, attn_caches) = jax.lax.scan(
                    group_body, x, (params["groups"], cache["groups"], cache["shared_attn"])
                )
            else:
                accs = []
                for i in range(g):
                    xs_i = jax.tree.map(
                        lambda a: a[i], (params["groups"], cache["groups"], cache["shared_attn"])
                    )
                    x, acc = group_body(x, xs_i)
                    accs.append(acc)
                ssm_caches, attn_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *accs)
            new_cache["groups"] = ssm_caches
            new_cache["shared_attn"] = attn_caches
            if tail:
                x, tail_caches = stack_step(x, params["tail"], cache["tail"], tail)
                new_cache["tail"] = tail_caches
        x = _norm(x, params, "final_norm", cfg)
        return self._logits(params, x).astype(jnp.float32), new_cache

    def decode_step_paged(self, params, cache, token, page_table, kv_len, live):
        """Paged decode step (dense/moe): token (B,), page_table (B, max_pages),
        kv_len (B,) tokens already cached per row, live (B,) bool.

        Every layer writes its new K/V at the same per-row position
        ``kv_len[b]`` — the caller (the serve loop's `PagedKVPool`) advances
        lengths once per step, after the step.  Dead rows (``live`` False)
        park their writes on the null page; their logits are garbage and the
        scheduler never bills them.  Returns (logits (B, V), new cache).
        """
        cfg, run = self.cfg, self.run
        x = self._embed(params, token, _dt(run))

        def body(h, xs):
            p_l, c_l = xs
            h, c_new = apply_layer_decode_paged(
                h, p_l, c_l, cfg, run, page_table, kv_len, live
            )
            return h, c_new

        if run.scan_layers:
            x, caches = jax.lax.scan(
                body, x, (params["layers"], cache["layers"]), length=cfg.n_layers
            )
        else:
            news = []
            for i in range(cfg.n_layers):
                p_l = jax.tree.map(lambda a: a[i], params["layers"])
                c_l = jax.tree.map(lambda a: a[i], cache["layers"])
                x, c_new = body(x, (p_l, c_l))
                news.append(c_new)
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *news)
        x = _norm(x, params, "final_norm", cfg)
        return self._logits(params, x).astype(jnp.float32), {"layers": caches}
