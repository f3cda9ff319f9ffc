"""Paged KV pool + paged decode-attention kernel.

Both decode kernels — the dense-slab `decode_attention` and the paged
one — are checked against the SAME ragged oracle (`ragged_decode_ref`),
so the ``kv_len == 0 -> exact zeros`` contract is pinned down once and
enforced twice.  The pool tests churn alloc/free/defrag and assert the
allocator invariants the serve loop depends on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import decode_attention
from repro.kernels.paged_attention import (
    NULL_PAGE,
    PagedKVPool,
    apply_page_permutation,
    gather_pages,
    init_page_arrays,
    pack_prefill_pages,
    paged_decode_attention,
    paged_decode_attention_ref,
    paged_tuner_model,
    pages_for,
    ragged_decode_ref,
)

TOL = dict(rtol=2e-2, atol=2e-3)
TOL32 = dict(rtol=1e-3, atol=1e-3)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _build_paged(rng, kv_lens, ps, max_pages, hkv, d, dtype):
    """Pool + page arrays + dense mirror for a batch of ragged lengths."""
    b = len(kv_lens)
    pool = PagedKVPool(n_pages=1 + b * max_pages, page_size=ps)
    kp, vp = init_page_arrays(pool.n_pages, ps, hkv, d, dtype)
    s = max_pages * ps
    kd = np.zeros((b, s, hkv, d), np.float32)
    vd = np.zeros_like(kd)
    slot_rids = []
    for r, ln in enumerate(kv_lens):
        if ln == 0:
            slot_rids.append(None)
            continue
        pages = pool.alloc(r, ln)
        assert pages is not None
        pool.note_tokens(r, ln)
        k = rng.normal(size=(ln, hkv, d)).astype(np.float32)
        v = rng.normal(size=(ln, hkv, d)).astype(np.float32)
        kd[r, :ln], vd[r, :ln] = k, v
        kp, vp = pack_prefill_pages(
            kp, vp, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(pages, jnp.int32),
        )
        slot_rids.append(r)
    table = jnp.asarray(pool.table(slot_rids, max_pages))
    lens = jnp.asarray(pool.kv_lens(slot_rids))
    return pool, kp, vp, table, lens, jnp.asarray(kd, dtype), jnp.asarray(vd, dtype)


# --------------------------------------------------------------------------- kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (ps, max_pages, Hq, Hkv, D, kv_lens, bk) — incl. 0, 1, ragged, exactly full
    (16, 4, 4, 4, 64, (0, 1, 37, 64), None),
    (32, 2, 8, 2, 64, (0, 33, 64), None),    # GQA group 4
    (8, 3, 4, 1, 128, (24, 5), None),        # MQA, exact page multiple
    # chunks of 3 pages over a 7-page table (the last chunk part past it),
    # qwen2.5-3b's heads: kv_len 0, 1, ps-1, ps, a chunk -1/+0/+1, full table
    (16, 7, 16, 2, 128, (0, 1, 15, 16, 47, 48, 49, 112), 48),
    # phi3-mini's heads (group 1, 32 KV heads of 96), dead rows between live
    # ones, chunks of 2 pages over 5
    (16, 5, 32, 32, 96, (0, 1, 15, 0, 16, 31, 32, 33, 80), 32),
    # group 1 at a lane-aligned head width (the manual-DMA walk)
    (16, 5, 8, 8, 128, (33, 0, 0, 16, 80, 0, 47), 32),
])
def test_paged_decode_matches_oracles(shape, dtype):
    ps, max_pages, hq, hkv, d, kv_lens, bk = shape
    rng = np.random.default_rng(sum(kv_lens) + ps)
    _, kp, vp, table, lens, kd, vd = _build_paged(
        rng, kv_lens, ps, max_pages, hkv, d, dtype
    )
    b = len(kv_lens)
    q = _rand(jax.random.PRNGKey(0), (b, hq, d), dtype)
    out = paged_decode_attention(q, kp, vp, table, lens, bk=bk)
    assert (np.asarray(out)[np.asarray(lens) == 0] == 0.0).all()
    tol = TOL32 if dtype == jnp.float32 else TOL
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(paged_decode_attention_ref(q, kp, vp, table, lens), np.float32),
        **tol,
    )
    # and vs the dense ragged oracle on the mirrored dense cache
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ragged_decode_ref(q, kd, vd, lens), np.float32),
        **tol,
    )


def test_paged_decode_kv0_rows_exact_zero():
    """Free/padded slots (kv_len == 0) must be *exact* zeros, never NaN."""
    rng = np.random.default_rng(0)
    _, kp, vp, table, lens, _, _ = _build_paged(
        rng, (0, 13, 0), 8, 2, 2, 32, jnp.float32
    )
    q = _rand(jax.random.PRNGKey(1), (3, 4, 32), jnp.float32)
    out = np.asarray(paged_decode_attention(q, kp, vp, table, lens))
    assert np.isfinite(out).all()
    assert (out[0] == 0.0).all() and (out[2] == 0.0).all()
    assert np.abs(out[1]).max() > 0.0


def test_paged_decode_sub_page_bk_tiling():
    rng = np.random.default_rng(2)
    _, kp, vp, table, lens, _, _ = _build_paged(
        rng, (40, 7, 64), 16, 4, 2, 64, jnp.float32
    )
    q = _rand(jax.random.PRNGKey(2), (3, 4, 64), jnp.float32)
    full = paged_decode_attention(q, kp, vp, table, lens)
    for bk in (4, 8, 32):  # bk > ps clamps down to ps
        tiled = paged_decode_attention(q, kp, vp, table, lens, bk=bk)
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(full), **TOL32)


def test_paged_decode_ref_dispatch():
    rng = np.random.default_rng(9)
    _, kp, vp, table, lens, _, _ = _build_paged(
        rng, (0, 11), 8, 2, 1, 32, jnp.float32
    )
    q = _rand(jax.random.PRNGKey(9), (2, 2, 32), jnp.float32)
    via_flag = paged_decode_attention(q, kp, vp, table, lens, use_pallas=False)
    np.testing.assert_array_equal(
        np.asarray(via_flag),
        np.asarray(paged_decode_attention_ref(q, kp, vp, table, lens)),
    )


def test_pack_prefill_pages_roundtrip():
    """pack -> gather returns the original rows (tail zero-padded)."""
    ps, hkv, d, s = 8, 2, 16, 21
    pool = PagedKVPool(n_pages=8, page_size=ps)
    kp, vp = init_page_arrays(pool.n_pages, ps, hkv, d, jnp.float32)
    pages = pool.alloc(0, s)
    k = jnp.asarray(np.random.default_rng(3).normal(size=(s, hkv, d)), jnp.float32)
    kp, vp = pack_prefill_pages(kp, vp, k, k * 2.0, jnp.asarray(pages, jnp.int32))
    table = jnp.asarray(pool.table([0], pages_for(s, ps)))
    got = gather_pages(kp, table)[0]
    np.testing.assert_array_equal(np.asarray(got[:s]), np.asarray(k))
    assert (np.asarray(got[s:]) == 0.0).all()
    np.testing.assert_array_equal(
        np.asarray(gather_pages(vp, table)[0][:s]), np.asarray(k) * 2.0
    )


def test_defrag_permutation_preserves_attention():
    rng = np.random.default_rng(4)
    pool, kp, vp, table, lens, _, _ = _build_paged(
        rng, (13, 5, 20, 7), 8, 3, 4, 32, jnp.float32
    )
    q = _rand(jax.random.PRNGKey(4), (4, 4, 32), jnp.float32)
    before = paged_decode_attention(q, kp, vp, table, lens)
    pool.free(1)
    pool.free(3)
    perm = pool.defrag()
    kp, vp = apply_page_permutation(kp, perm), apply_page_permutation(vp, perm)
    slot_rids = [0, None, 2, None]
    table2 = jnp.asarray(pool.table(slot_rids, 3))
    lens2 = jnp.asarray(pool.kv_lens(slot_rids))
    after = paged_decode_attention(q, kp, vp, table2, lens2)
    keep = np.array([0, 2])
    np.testing.assert_array_equal(np.asarray(after[keep]), np.asarray(before[keep]))
    assert (np.asarray(after[np.array([1, 3])]) == 0.0).all()
    # defrag left the pool compact: pages 1..in_use are exactly the owned set
    owned = sorted(p for r in pool.rids for p in pool.pages_of(r))
    assert owned == list(range(1, pool.in_use + 1))


# --------------------------------------------------------------------------- shared-oracle property
@settings(max_examples=15, deadline=None)
@given(
    ps=st.sampled_from([8, 16]),
    max_pages=st.sampled_from([2, 4]),
    group=st.sampled_from([1, 2, 4]),
    hkv=st.sampled_from([1, 2]),
    lens_seed=st.integers(0, 2**16),
)
def test_both_kernels_share_one_ragged_oracle(ps, max_pages, group, hkv, lens_seed):
    """Dense `decode_attention` and the paged kernel vs ONE oracle, on the
    same ragged batch — kv_len drawn to include 0 and the full length S."""
    d = 32
    s = ps * max_pages
    rng = np.random.default_rng(lens_seed)
    b = int(rng.integers(2, 5))
    kv_lens = [0, s] + [int(rng.integers(0, s + 1)) for _ in range(b - 2)]
    _, kp, vp, table, lens, kd, vd = _build_paged(
        rng, tuple(kv_lens), ps, max_pages, hkv, d, jnp.float32
    )
    q = jnp.asarray(rng.normal(size=(b, group * hkv, d)), jnp.float32)
    oracle = ragged_decode_ref(q, kd, vd, lens)
    dense_out = decode_attention(q, kd, vd, lens, bk=ps)
    paged_out = paged_decode_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(dense_out), np.asarray(oracle), **TOL32)
    np.testing.assert_allclose(np.asarray(paged_out), np.asarray(oracle), **TOL32)
    zero = np.asarray(lens) == 0
    assert (np.asarray(dense_out)[zero] == 0.0).all()
    assert (np.asarray(paged_out)[zero] == 0.0).all()


# --------------------------------------------------------------------------- pool
def test_pool_alloc_free_reuse_and_stats():
    pool = PagedKVPool(n_pages=5, page_size=4)  # 4 usable pages
    p0 = pool.alloc(0, 6)  # 2 pages
    assert p0 is not None and len(p0) == 2 and NULL_PAGE not in p0
    pool.note_tokens(0, 6)
    assert pool.kv_len(0) == 6 and pool.capacity_tokens(0) == 8
    p1 = pool.alloc(1, 8)
    assert p1 is not None and not set(p0) & set(p1)
    assert pool.alloc(2, 5) is None  # all-or-nothing: 2 pages wanted, 0 left
    assert pool.stats().alloc_failures == 1
    assert 2 not in pool.rids  # refused alloc left no state behind
    freed = pool.free(0)
    assert freed == 2
    p2 = pool.alloc(2, 4)
    assert p2 is not None and set(p2) <= set(p0)  # LIFO reuse of hot pages
    st_ = pool.stats()
    assert st_.in_use == 3 and st_.free == 1
    assert st_.reused_pages >= 1 and st_.high_water == 4
    assert st_.frees == 2


def test_pool_append_extends_and_reports_oom():
    pool = PagedKVPool(n_pages=3, page_size=2)
    pool.alloc(0, 2)
    assert pool.append(0) and pool.append(0)  # fills page 1
    assert pool.append(0)  # auto-extends into the last free page
    assert pool.kv_len(0) == 3 and len(pool.pages_of(0)) == 2
    assert pool.append(0)  # fills page 2
    assert not pool.append(0)  # pool exhausted: reported, not raised
    assert pool.kv_len(0) == 4


def test_pool_guards():
    pool = PagedKVPool(n_pages=4, page_size=2)
    pool.alloc(7, 3)
    with pytest.raises(KeyError):
        pool.alloc(7, 1)  # double admission
    with pytest.raises(ValueError):
        pool.note_tokens(7, 5)  # beyond the 2-page reservation
    with pytest.raises(ValueError):
        pool.table_row(7, 1)  # table too narrow for the reservation
    with pytest.raises(ValueError):
        PagedKVPool(n_pages=1, page_size=2)  # only the null page
    row = pool.table_row(None, 3)
    assert (row == NULL_PAGE).all() and row.dtype == np.int32


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), n_pages=st.sampled_from([5, 9, 17]))
def test_pool_churn_invariants(seed, n_pages):
    """Random admit/append/free/defrag churn never breaks the allocator:
    no page owned twice, the null page never granted, free + in_use
    conserved, and freed pages become allocatable again."""
    rng = np.random.default_rng(seed)
    pool = PagedKVPool(n_pages=n_pages, page_size=4)
    live: list[int] = []
    next_rid = 0
    for _ in range(60):
        op = rng.integers(4)
        if op == 0:
            pages = pool.alloc(next_rid, int(rng.integers(1, 9)))
            if pages is not None:
                live.append(next_rid)
            next_rid += 1
        elif op == 1 and live:
            pool.append(live[int(rng.integers(len(live)))], int(rng.integers(1, 3)))
        elif op == 2 and live:
            pool.free(live.pop(int(rng.integers(len(live)))))
        elif op == 3:
            perm = pool.defrag()
            assert perm[NULL_PAGE] == NULL_PAGE
            assert sorted(perm.tolist()) == list(range(n_pages))
        owned = [p for r in pool.rids for p in pool.pages_of(r)]
        assert len(owned) == len(set(owned)), "page owned twice"
        assert NULL_PAGE not in owned, "null page granted"
        assert len(owned) == pool.in_use
        assert pool.in_use + pool.stats().free == n_pages - 1
        assert set(pool.rids) == set(live)
    for rid in list(live):
        pool.free(rid)
    assert pool.in_use == 0 and pool.stats().free == n_pages - 1


# --------------------------------------------------------------------------- model integration
def test_model_paged_decode_matches_dense_decode():
    """decode_step_paged == decode_step when every slot is admitted at pos 0."""
    from repro.configs import RunConfig, smoke_config
    from repro.models.transformer import DecoderLM

    cfg = smoke_config("qwen25-3b")  # dense GQA smoke
    run = RunConfig(compute_dtype="float32", decode_cache_dtype="float32")
    model = DecoderLM(cfg, run)
    params = model.init(jax.random.PRNGKey(0))
    b, s, ps, max_pages = 3, 7, 8, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    logits, cache_d = model.prefill(params, toks, max_len=ps * max_pages)

    pool = PagedKVPool(n_pages=1 + b * max_pages, page_size=ps)
    pcache = model.init_paged_cache(pool.n_pages, ps)
    kp, vp = pcache["layers"]["k"], pcache["layers"]["v"]
    for r in range(b):
        pages = pool.alloc(r, s + 3)
        pool.note_tokens(r, s)
        kp, vp = pack_prefill_pages(
            kp, vp, cache_d["layers"]["k"][:, r, :s], cache_d["layers"]["v"][:, r, :s],
            jnp.asarray(pages, jnp.int32),
        )
    pcache = {"layers": {"k": kp, "v": vp}}
    live = jnp.ones((b,), bool)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    slots = list(range(b))
    for _ in range(3):
        table = jnp.asarray(pool.table(slots, max_pages))
        lens = jnp.asarray(pool.kv_lens(slots))
        lg_d, cache_d = model.decode_step(params, cache_d, tok)
        lg_p, pcache = model.decode_step_paged(params, pcache, tok, table, lens, live)
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d), rtol=2e-4, atol=2e-4)
        for r in range(b):
            assert pool.append(r)
        tok = jnp.argmax(lg_d, -1).astype(jnp.int32)


def test_init_paged_cache_rejects_attention_free_families():
    from repro.configs import RunConfig, smoke_config
    from repro.models.transformer import DecoderLM

    model = DecoderLM(smoke_config("rwkv6-3b"), RunConfig())
    with pytest.raises(ValueError, match="paged"):
        model.init_paged_cache(8, 16)


# --------------------------------------------------------------------------- tuner model
def test_paged_tuner_model_cost_tradeoffs():
    from repro.power.tpu_model import DvfsState, TpuChipSpec

    model = paged_tuner_model(b=8, kv_mean=100.0)
    chip = TpuChipSpec()
    dvfs = DvfsState()
    assert set(model.search_space) == {"page_size", "bk", "depth"}
    t_small, c_small = model.model({"page_size": 32, "bk": 32, "depth": 2}, chip, dvfs)
    t_big, c_big = model.model({"page_size": 256, "bk": 128, "depth": 2}, chip, dvfs)
    # bigger pages over-fetch more bytes on ragged tails...
    assert c_big.hbm_bytes > c_small.hbm_bytes
    # ...while small pages pay more per-block issue latency
    t1, _ = model.model({"page_size": 32, "bk": 32, "depth": 1}, chip, dvfs)
    t4, _ = model.model({"page_size": 32, "bk": 32, "depth": 4}, chip, dvfs)
    assert t4 < t1
    assert t_small > 0 and t_big > 0
