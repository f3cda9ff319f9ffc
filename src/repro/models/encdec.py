"""Whisper-style encoder-decoder (audio frontend stubbed per assignment).

Encoder: bidirectional attention over precomputed frame embeddings
(B, T_enc, d) — the conv1d×2 stem is a STUB supplied by `input_specs()`.
Decoder: causal self-attention + cross-attention + GELU MLP.
Sinusoidal positions on the encoder, learned on the decoder (whisper-
faithful); pre-LN layernorms (with bias, as whisper uses LayerNorm).

Serve path: ``encode`` (the enc-dec "prefill": encoder pass + cross-KV
precompute), then ``decode_step`` against self+cross caches.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, RunConfig

from .layers import attention, full_attention, layernorm, mlp_gelu
from .params import cast_tree, dense_init, embed_init, stack_layers
from .transformer import _dt, _qkv, init_attn


def _ln_init(d):
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def init_enc_layer(key, cfg: ArchConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": _ln_init(cfg.d_model),
        "attn": init_attn(k1, cfg),
        "ln2": _ln_init(cfg.d_model),
        "wi": dense_init(k2, cfg.d_model, cfg.d_ff),
        "wo2": dense_init(k3, cfg.d_ff, cfg.d_model),
    }


def init_dec_layer(key, cfg: ArchConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "ln1": _ln_init(cfg.d_model),
        "self_attn": init_attn(k1, cfg),
        "ln_x": _ln_init(cfg.d_model),
        "cross_attn": init_attn(k2, cfg),
        "ln2": _ln_init(cfg.d_model),
        "wi": dense_init(k3, cfg.d_model, cfg.d_ff),
        "wo2": dense_init(k4, cfg.d_ff, cfg.d_model),
    }


def sinusoid_positions(t: int, d: int) -> jax.Array:
    pos = jnp.arange(t)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def _ln(x, p, eps):
    return layernorm(x, p["w"], p["b"], eps)


def _mha(x, kv_src, p, cfg, run, causal):
    """Attention where K/V come from kv_src (cross if != x)."""
    b, s, _ = x.shape
    q, _, _ = _qkv(x, p, cfg, None, rope=False)
    _, k, v = _qkv(kv_src, p, cfg, None, rope=False)
    if run.attn_impl == "full" or s % run.q_chunk or kv_src.shape[1] % run.kv_chunk or s != kv_src.shape[1]:
        o = full_attention(q, k, v, causal=causal)
    else:
        o = attention(
            q, k, v, impl="chunked", causal=causal,
            q_chunk=run.q_chunk, kv_chunk=run.kv_chunk, unroll=run.scan_unroll,
            skip_masked_blocks=run.skip_masked_blocks and causal,
        )
    return jnp.einsum("bse,ed->bsd", o.reshape(b, s, -1), p["wo"].astype(x.dtype)), (k, v)


@dataclass
class EncDecLM:
    cfg: ArchConfig
    run: RunConfig = RunConfig()

    def init(self, key) -> dict:
        """Random parameters in ``run.param_dtype`` (see `DecoderLM.init`)."""
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        params = {
            "enc_in": dense_init(ks[0], cfg.d_model, cfg.d_model),  # frame adapter (stub stem)
            "embed": embed_init(ks[1], cfg.vocab_padded, cfg.d_model),
            "dec_pos": 0.01 * jax.random.normal(ks[2], (32768, cfg.d_model), jnp.float32),
            "enc_layers": stack_layers(lambda k: init_enc_layer(k, cfg), ks[3], cfg.enc_layers),
            "dec_layers": stack_layers(lambda k: init_dec_layer(k, cfg), ks[4], cfg.dec_layers),
            "enc_norm": _ln_init(cfg.d_model),
            "dec_norm": _ln_init(cfg.d_model),
        }
        return cast_tree(params, self.run.param_dtype)

    # ------------------------------------------------------------- encoder
    def encode(self, params, frames):
        """frames: (B, T_enc, d) stub embeddings. Returns encoder output."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        x = jnp.einsum("btd,de->bte", frames.astype(dtype), params["enc_in"].astype(dtype))
        x = x + sinusoid_positions(x.shape[1], cfg.d_model).astype(dtype)[None]

        def body(h, p_l):
            a, _ = _mha(_ln(h, p_l["ln1"], cfg.norm_eps), _ln(h, p_l["ln1"], cfg.norm_eps),
                        p_l["attn"], cfg, run, causal=False)
            h = h + a
            m = mlp_gelu(_ln(h, p_l["ln2"], cfg.norm_eps), p_l["wi"], p_l["wo2"])
            return h + m, None

        body_fn = jax.checkpoint(body) if run.remat == "layer" else body
        if run.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: body_fn(h, p), x, params["enc_layers"])
        else:
            for i in range(cfg.enc_layers):
                x, _ = body_fn(x, jax.tree.map(lambda a: a[i], params["enc_layers"]))
        return _ln(x, params["enc_norm"], cfg.norm_eps)

    # ------------------------------------------------------------- decoder
    def _dec_stack(self, params, x, enc_out, collect_caches: bool):
        cfg, run = self.cfg, self.run

        def body(h, p_l):
            a, (sk, sv) = _mha(
                _ln(h, p_l["ln1"], cfg.norm_eps), _ln(h, p_l["ln1"], cfg.norm_eps),
                p_l["self_attn"], cfg, run, causal=True,
            )
            h = h + a
            c, (ck, cv) = _mha(
                _ln(h, p_l["ln_x"], cfg.norm_eps), enc_out, p_l["cross_attn"], cfg, run,
                causal=False,
            )
            h = h + c
            m = mlp_gelu(_ln(h, p_l["ln2"], cfg.norm_eps), p_l["wi"], p_l["wo2"])
            cdt = jnp.dtype(run.decode_cache_dtype)
            cache = {
                "self_k": sk.astype(cdt), "self_v": sv.astype(cdt),
                "cross_k": ck.astype(cdt), "cross_v": cv.astype(cdt),
            }
            return h + m, cache

        body_fn = jax.checkpoint(body) if run.remat == "layer" else body
        if run.scan_layers:
            x, caches = jax.lax.scan(body_fn, x, params["dec_layers"])
        else:
            accs = []
            for i in range(cfg.dec_layers):
                x, c = body_fn(x, jax.tree.map(lambda a: a[i], params["dec_layers"]))
                accs.append(c)
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *accs)
        return _ln(x, params["dec_norm"], cfg.norm_eps), caches

    def _dec_logits(self, params, x):
        return jnp.einsum("...d,vd->...v", x, params["embed"].astype(x.dtype))

    # ------------------------------------------------------------- train
    def loss_fn(self, params, batch):
        """batch: {'frames': (B,T_enc,d), 'tokens': (B,T_dec+1)}."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embed"].astype(dtype)[inputs]
        x = x + params["dec_pos"][: x.shape[1]].astype(dtype)[None]
        x, _ = self._dec_stack(params, x, enc_out, collect_caches=False)
        logits = self._dec_logits(params, x).astype(jnp.float32)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = (lz - gold).mean()
        return loss, {"ce": loss}

    # ------------------------------------------------------------- serve
    def prefill(self, params, batch, max_len: int | None = None):
        """Encoder pass + decoder prefill over prompt tokens."""
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        enc_out = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        max_len = max_len or s
        x = params["embed"].astype(dtype)[tokens]
        x = x + params["dec_pos"][:s].astype(dtype)[None]
        x, caches = self._dec_stack(params, x, enc_out, collect_caches=True)
        logits = self._dec_logits(params, x[:, -1]).astype(jnp.float32)

        def pad_self(a):
            if a.shape[2] == max_len:
                return a
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, max_len - a.shape[2])
            return jnp.pad(a, pad)

        cache = {
            "self_k": pad_self(caches["self_k"]), "self_v": pad_self(caches["self_v"]),
            "cross_k": caches["cross_k"], "cross_v": caches["cross_v"],
            "pos": jnp.int32(s),
        }
        return logits, cache

    def init_cache(self, batch: int, max_len: int, enc_len: int):
        cfg, run = self.cfg, self.run
        cdt = jnp.dtype(run.decode_cache_dtype)
        hkv, hd, L = cfg.n_kv_heads, cfg.head_dim_, cfg.dec_layers
        return {
            "self_k": jnp.zeros((L, batch, max_len, hkv, hd), cdt),
            "self_v": jnp.zeros((L, batch, max_len, hkv, hd), cdt),
            "cross_k": jnp.zeros((L, batch, enc_len, hkv, hd), cdt),
            "cross_v": jnp.zeros((L, batch, enc_len, hkv, hd), cdt),
            "pos": jnp.int32(0),
        }

    def decode_step(self, params, cache, token):
        cfg, run = self.cfg, self.run
        dtype = _dt(run)
        b = token.shape[0]
        pos = cache["pos"]
        x = params["embed"].astype(dtype)[token]
        x = x + jax.lax.dynamic_index_in_dim(params["dec_pos"], pos, keepdims=False).astype(dtype)

        def body(h, xs):
            p_l, c_l = xs
            hn = _ln(h[:, None], p_l["ln1"], cfg.norm_eps)
            q, k, v = _qkv(hn, p_l["self_attn"], cfg, None, rope=False)
            cdt = c_l["self_k"].dtype
            sk = jax.lax.dynamic_update_slice_in_dim(c_l["self_k"], k.astype(cdt), pos, axis=1)
            sv = jax.lax.dynamic_update_slice_in_dim(c_l["self_v"], v.astype(cdt), pos, axis=1)
            o = full_attention(
                q, sk.astype(q.dtype), sv.astype(q.dtype), causal=False,
                kv_len=jnp.full((b,), pos + 1),
            ).reshape(b, -1)
            h = h + o @ p_l["self_attn"]["wo"].astype(dtype)
            hn = _ln(h[:, None], p_l["ln_x"], cfg.norm_eps)
            q, _, _ = _qkv(hn, p_l["cross_attn"], cfg, None, rope=False)
            o = full_attention(
                q, c_l["cross_k"].astype(q.dtype), c_l["cross_v"].astype(q.dtype), causal=False
            ).reshape(b, -1)
            h = h + o @ p_l["cross_attn"]["wo"].astype(dtype)
            m = mlp_gelu(_ln(h[:, None], p_l["ln2"], cfg.norm_eps), p_l["wi"], p_l["wo2"])[:, 0]
            return h + m, {"self_k": sk, "self_v": sv}

        if run.scan_layers:
            x, updates = jax.lax.scan(
                body, x, (params["dec_layers"],
                          {"self_k": cache["self_k"], "self_v": cache["self_v"],
                           "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]})
            )
        else:
            ups = []
            for i in range(cfg.dec_layers):
                xs = jax.tree.map(
                    lambda a: a[i],
                    (params["dec_layers"],
                     {"self_k": cache["self_k"], "self_v": cache["self_v"],
                      "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}),
                )
                x, u = body(x, xs)
                ups.append(u)
            updates = jax.tree.map(lambda *xs: jnp.stack(xs), *ups)
        x = _ln(x[:, None], params["dec_norm"], cfg.norm_eps)[:, 0]
        logits = self._dec_logits(params, x).astype(jnp.float32)
        new_cache = dict(cache)
        new_cache.update({"self_k": updates["self_k"], "self_v": updates["self_v"],
                          "pos": pos + 1})
        return logits, new_cache
