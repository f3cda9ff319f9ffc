"""Mixture-of-Experts layer: top-k routing with two dispatch strategies.

* ``einsum`` — GShard/gspmd-style one-hot dispatch/combine einsums with a
  per-group capacity.  Simple, sharding-friendly, but the dense one-hot
  dispatch tensors cost real FLOPs/bytes (visible in the roofline's
  MODEL_FLOPS/HLO_FLOPS ratio — deliberately kept as the baseline).
* ``sort``   — argsort-based dispatch: tokens are sorted by expert id and
  gathered into (E, capacity) slots without any dense one-hot product.
  The beyond-paper optimisation used in §Perf hillclimbing.

Both are capacity-based (static shapes; overflow tokens are dropped and
their residual passes through — standard practice at scale). Training
uses them, with the configuration's router.

Serving (prefill and decode) uses ``moe_dropless``: every live token's
pairs reach their experts, sorted by expert into one grouped matmul over
the experts held here (`repro.kernels.moe_gmm`). The router chooses
among all the model's experts; a layer that holds a share of them (the
expert-parallel cut) computes that share's part of the result alone.

Routers: ``topk`` (softmax, top-k, renormalised) and ``sparsemixer``
(Phi-3.5-MoE's top-2 at inference, `sparsemixer_top2`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def router_topk(x, w_router, k: int, router: str = "topk", eps: float = 0.01):
    """x: (T, d) -> (gates (T,k) f32, idx (T,k) int32, logits (T,E))."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32))
    gates, idx = route(logits, k, router, eps)
    return gates, idx, logits


def route(logits, k: int, router: str = "topk", eps: float = 0.01):
    """(T, E) router logits -> (gates (T, k) f32, experts (T, k) int32)."""
    if router == "sparsemixer":
        if k != 2:
            raise ValueError(f"sparsemixer routes each token to 2 experts, not {k}")
        return sparsemixer_top2(logits, eps)
    if router != "topk":
        raise ValueError(f"unknown router {router!r}")
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9), idx


def _sparsemixer_pick(fill, s, eps):
    """The best expert of ``fill`` and its weight: the softmax of ``fill`` with
    every expert i masked out where (top - s_i) / max(|s_i|, top) > 2 eps."""
    e = jnp.argmax(fill, axis=-1)
    top = jnp.take_along_axis(fill, e[:, None], axis=-1)
    masked = jnp.where((top - s) / jnp.maximum(jnp.abs(s), top) > 2 * eps, -jnp.inf, fill)
    w = jnp.take_along_axis(jax.nn.softmax(masked, axis=-1), e[:, None], axis=-1)[:, 0]
    return w, e.astype(jnp.int32)


def sparsemixer_top2(logits, eps: float):
    """Phi-3.5-MoE's router at inference (``router_jitter_noise`` = ``eps``).

    The first expert is the best logit, weighted by the softmax over the
    experts within 2 eps of it (relatively); the second is the best of the
    rest, weighted likewise over the rest, the threshold measured against
    the original logits. The two weights are not renormalised.
    """
    s = logits.astype(jnp.float32)
    w1, e1 = _sparsemixer_pick(s, s, eps)
    rest = jnp.where(jnp.arange(s.shape[-1]) == e1[:, None], -jnp.inf, s)
    w2, e2 = _sparsemixer_pick(rest, s, eps)
    return jnp.stack([w1, w2], axis=-1), jnp.stack([e1, e2], axis=-1)


def load_balancing_loss(logits: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / idx.size
    p = probs.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_ffn(xe, wi, wg, wo):
    """xe: (E, C, d); expert weights (E, d, f) / (E, f, d)."""
    h = jnp.einsum("ecd,edf->ecf", xe, wi.astype(xe.dtype))
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo.astype(xe.dtype))


def moe_einsum(x, params, n_experts: int, k: int, capacity_factor: float = 1.25,
               group_size: int = 512, router: str = "topk", eps: float = 0.01):
    """GShard-style dispatch. x: (B, S, d) -> (B, S, d), aux_loss.

    Memory-sane einsum form: the (g, gs, E, C) dispatch/combine one-hots
    are built per top-k slot (never materialising a 5-D (g,gs,k,E,C)
    tensor) and cast to the compute dtype.  The dense dispatch matmuls
    still cost real FLOPs — that is the measured baseline pathology the
    `sort` implementation removes in §Perf.
    """
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gates, idx, logits = router_topk(xf, params["router"], k, router, eps)
    aux = load_balancing_loss(logits, idx, n_experts)

    g = max(1, t // group_size)
    gs = t // g
    cap = max(int(capacity_factor * k * gs / n_experts), 1)

    xg = xf.reshape(g, gs, d)
    idx_g = idx.reshape(g, gs, k)
    gates_g = gates.reshape(g, gs, k)

    # position of each (token, slot) within its expert's capacity: the
    # joint cumsum over the flattened (token, slot) order (small int math)
    onehot_e = jax.nn.one_hot(idx_g, n_experts, dtype=jnp.float32)  # (g,gs,k,E)
    flat = onehot_e.reshape(g, gs * k, n_experts)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(g, gs, k, n_experts)
    pos_of_slot = jnp.sum(pos * onehot_e, axis=-1).astype(jnp.int32)  # (g,gs,k)
    in_cap = pos_of_slot < cap

    dt = x.dtype
    y = jnp.zeros_like(xg)
    xe_sum = jnp.zeros((g, n_experts, cap, d), dt)
    dispatches = []
    for j in range(k):  # per-slot (g,gs,E,C) one-hots, bf16
        d_j = (
            onehot_e[:, :, j, :, None]
            * jax.nn.one_hot(pos_of_slot[:, :, j], cap, dtype=jnp.float32)[:, :, None, :]
            * in_cap[:, :, j, None, None]
        ).astype(dt)
        dispatches.append(d_j)
        xe_sum = xe_sum + jnp.einsum("gsec,gsd->gecd", d_j, xg)
    ye = jax.vmap(_expert_ffn, in_axes=(0, None, None, None))(
        xe_sum, params["wi"], params["wg"], params["wo"]
    )  # (g,E,C,d)
    for j in range(k):
        combine_j = dispatches[j] * gates_g[:, :, j, None, None].astype(dt)
        y = y + jnp.einsum("gsec,gecd->gsd", combine_j, ye)
    return y.reshape(b, s, d), aux


def moe_sort(x, params, n_experts: int, k: int, capacity_factor: float = 1.25,
             group_size: int = 4096, router: str = "topk", eps: float = 0.01):
    """Sort-based dispatch: no dense one-hot matmuls.

    Within each group: flatten (token, slot) pairs, sort by expert id,
    scatter the first `cap` arrivals per expert into (E, cap) slots, run
    the grouped expert FFN, and scatter-add weighted results back.
    """
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gates, idx, logits = router_topk(xf, params["router"], k, router, eps)
    aux = load_balancing_loss(logits, idx, n_experts)

    g = max(1, t // group_size)
    gs = t // g
    cap = max(int(capacity_factor * k * gs / n_experts), 1)

    def per_group(xg, idx_g, gates_g):
        # xg: (gs, d); idx_g/gates_g: (gs, k)
        flat_e = idx_g.reshape(-1)  # (gs*k,)
        flat_tok = jnp.repeat(jnp.arange(gs), k)
        flat_gate = gates_g.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        tok_sorted = flat_tok[order]
        gate_sorted = flat_gate[order]
        # position within expert = rank - first-rank-of-expert
        first_of_e = jnp.searchsorted(e_sorted, jnp.arange(n_experts))
        pos_in_e = jnp.arange(gs * k) - first_of_e[e_sorted]
        keep = pos_in_e < cap
        slot = jnp.where(keep, e_sorted * cap + pos_in_e, n_experts * cap)  # overflow -> dump slot
        # gather tokens into (E*cap (+1 dump), d)
        xe = jnp.zeros((n_experts * cap + 1, d), xf.dtype).at[slot].set(xg[tok_sorted])
        xe = xe[:-1].reshape(n_experts, cap, d)
        ye = _expert_ffn(xe, params["wi"], params["wg"], params["wo"])  # (E,cap,d)
        ye_flat = jnp.concatenate([ye.reshape(n_experts * cap, d),
                                   jnp.zeros((1, d), ye.dtype)], axis=0)
        contrib = ye_flat[slot] * gate_sorted[:, None].astype(ye.dtype) * keep[:, None]
        y = jnp.zeros((gs, d), ye.dtype).at[tok_sorted].add(contrib)
        return y

    xg = xf.reshape(g, gs, d)
    y = jax.vmap(per_group)(xg, idx.reshape(g, gs, k), gates.reshape(g, gs, k))
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_layer(x, params, n_experts: int, k: int, capacity_factor: float = 1.25,
              impl: str = "einsum", group_size: int | None = None, router: str = "topk",
              eps: float = 0.01):
    if impl == "einsum":
        return moe_einsum(x, params, n_experts, k, capacity_factor,
                          group_size=group_size or 1024, router=router, eps=eps)
    if impl == "sort":
        return moe_sort(x, params, n_experts, k, capacity_factor,
                        group_size=group_size or 4096, router=router, eps=eps)
    raise ValueError(f"unknown moe impl {impl!r}")


def moe_dropless(x, params, n_experts: int, k: int, first: int = 0,
                 router: str = "topk", eps: float = 0.01, live=None):
    """Every live token's part of the layer from the experts held here.

    x: (T, d); ``params``: ``router`` (d, n_experts) over every expert, and
    ``wi``, ``wg``, ``wo`` of the held experts ``first`` to ``first + held``.
    The router scores every expert in float32; each token's k (token,
    expert) pairs are sorted by expert, the held experts' rows go through
    one grouped matmul each for ``wi``, ``wg`` and ``wo``, and ``gate *
    expert(x)`` is added back to the token. Pairs of experts held elsewhere
    add nothing here. Rows where ``live`` (T,) is False make no pairs.
    Returns (T, d) in x's dtype.
    """
    from repro.kernels.moe_gmm import held_expert_ffn

    t, d = x.shape
    logits = jnp.dot(x.astype(jnp.float32), params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, idx = route(logits, k, router, eps)
    if live is not None:  # n_experts: past every group, so in none
        idx = jnp.where(live[:, None], idx, n_experts)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1, mode="drop")
    y = held_expert_ffn(x[tok], params["wi"], params["wg"], params["wo"], sizes, first)
    y = y * gates.reshape(-1)[order][:, None]
    return jnp.zeros((t, d), jnp.float32).at[tok].add(y).astype(x.dtype)
