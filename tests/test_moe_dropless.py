"""The serving expert layer: sparsemixer routing, the held-expert grouped
matmul (interpret mode on the CPU) and the dropless layer built on them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import held_expert_ffn, held_expert_gmm, held_expert_gmm_ref
from repro.models.moe import moe_dropless, moe_layer, sparsemixer_top2


def sparsemixer_by_the_rule(s, eps):
    """Phi-3.5-MoE's top-2 rule, transcribed one token at a time."""
    s = [float(v) for v in s]
    n = len(s)

    def pick(fill):
        e = max(range(n), key=lambda i: (fill[i], -i))  # the first of equal bests
        top = fill[e]
        # 0 / 0 (a best of 0 beside a score of 0) masks nothing
        far = [max(abs(s[i]), top) != 0 and (top - s[i]) / max(abs(s[i]), top) > 2 * eps
               for i in range(n)]
        kept = [-np.inf if far[i] else fill[i] for i in range(n)]
        z = sum(np.exp(v - top) for v in kept)
        return np.exp(kept[e] - top) / z, e

    w1, e1 = pick(s)
    w2, e2 = pick([-np.inf if i == e1 else s[i] for i in range(n)])
    return (w1, w2), (e1, e2)


def test_sparsemixer_matches_the_rule_including_masked_out_ties():
    rng = np.random.default_rng(0)
    rows = [rng.normal(size=16) for _ in range(64)]
    rows += [
        [2.0, 2.0, 1.0, -1.0],  # a tie for the best: the first wins, the other comes second
        [2.0, 1.99, 1.95, 0.5],  # 1.99 within 2 eps of 2.0, 1.95 not
        [1.0, 0.5, 0.5, 0.4],  # a tie for second, both masked out of the first pick
        [-0.5, -0.5, -0.51, -3.0],  # all negative: the threshold divides by |s_i|
        [0.0, 0.0, -1.0, -2.0],  # 0 / 0 masks nothing
    ]
    for s in rows:
        want_w, want_e = sparsemixer_by_the_rule(s, 0.01)
        w, e = sparsemixer_top2(jnp.asarray([s], jnp.float32), 0.01)
        assert tuple(int(x) for x in e[0]) == want_e, s
        np.testing.assert_allclose(np.asarray(w[0]), want_w, rtol=1e-6, err_msg=str(s))


@pytest.mark.parametrize("sizes,first,held,m", [
    ([3, 0, 5, 2], 0, 4, 12),  # an empty group, padded rows after the last
    ([3, 0, 5, 2, 0, 4], 2, 3, 17),  # a held range from expert 2, rows of experts held elsewhere
    ([0, 0, 7, 1], 1, 2, 8),  # the first held expert empty
    ([40, 1, 0, 90], 0, 4, 131),  # groups across row tiles of 128
    ([0, 0, 0, 0], 0, 4, 5),  # no rows at all
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_held_expert_gmm_matches_one_einsum_per_expert(sizes, first, held, m, dtype):
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.normal(size=(m, 32)), dtype)
    rhs = jnp.asarray(rng.normal(size=(held, 32, 48)), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = held_expert_gmm(lhs, rhs, sizes, first)
    want = held_expert_gmm_ref(lhs, rhs, sizes, first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_held_expert_ffn_is_swiglu_per_row_expert():
    rng = np.random.default_rng(2)
    sizes = jnp.asarray([2, 4, 0, 3], jnp.int32)
    x = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    wi, wg = (jnp.asarray(rng.normal(size=(2, 16, 24)), jnp.float32) for _ in range(2))
    wo = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = held_expert_ffn(x, wi, wg, wo, sizes, first=1)
        expert = np.repeat([0, 1, 2, 3, 4], [2, 4, 0, 3, 2])  # 4: padding past every group
        for r in range(11):
            j = expert[r] - 1
            want = (jax.nn.silu(x[r] @ wg[j]) * (x[r] @ wi[j])) @ wo[j] if j in (0, 1) else 0
            np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want), rtol=1e-5,
                                       atol=1e-5)


def _params(key, d, ff, e, held):
    ks = jax.random.split(key, 4)
    n = jax.random.normal
    return {"router": n(ks[0], (d, e)), "wi": n(ks[1], (held, d, ff)) / d**0.5,
            "wg": n(ks[2], (held, d, ff)) / d**0.5, "wo": n(ks[3], (held, ff, d)) / ff**0.5}


def test_dropless_matches_capacity_dispatch_when_nothing_drops():
    """Every expert held, softmax top-2: the same layer as the einsum dispatch
    with room for every pair."""
    p = _params(jax.random.PRNGKey(0), 32, 48, 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = moe_layer(x, p, 4, 2, capacity_factor=4.0, group_size=24)
        got = moe_dropless(x.reshape(24, 32), p, 4, 2).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_dead_rows_make_no_pairs():
    p = _params(jax.random.PRNGKey(3), 32, 48, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, True])
    with jax.default_matmul_precision("highest"):
        got = moe_dropless(x, p, 8, 2, first=4, router="sparsemixer", live=live)
        alone = moe_dropless(x[live], p, 8, 2, first=4, router="sparsemixer")
    assert float(jnp.abs(got[~live]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got[live]), np.asarray(alone), rtol=1e-6, atol=1e-6)
