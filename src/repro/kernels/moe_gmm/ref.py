"""Oracle for the held-expert grouped matmul: one einsum per held expert."""
from __future__ import annotations

import jax.numpy as jnp


def held_expert_gmm_ref(lhs, rhs, group_sizes, first=0):
    """Each row times the weights of its expert where that expert is held
    (``first`` to ``first + rhs.shape[0]``); zeros elsewhere. float32."""
    ends = jnp.cumsum(group_sizes)
    expert = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for j in range(rhs.shape[0]):
        y = jnp.einsum("mk,kn->mn", lhs.astype(jnp.float32), rhs[j].astype(jnp.float32))
        out = jnp.where((expert == first + j)[:, None], y, out)
    return out
