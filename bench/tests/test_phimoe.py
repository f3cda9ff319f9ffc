"""Phi-3.5-MoE's model module, reference and serving path on the CPU at smoke widths.

The served path is the real one (`bench.serving.Engine`: prefill, pack into
pages, paged decode under slot churn), with Pallas in interpret mode.
"""
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run
from bench.drivers import backlog
from bench.models import phimoe
from bench.reference import adapter
from bench.reference import phimoe as reference
from bench.serving import Engine, Source, serve
from bench.tests.conftest import SMOKE_MIXES, smoke_ctx
from bench.traffic import Backlog
from bench.weights import arch_config, load_config, make_params
from repro.launch.serve import SERVE_RUN
from repro.models import build_model

#: published keys at smoke widths: 8 experts for the router, 4 held from 4
SMOKE = {"name": "smoke-moe", "model": "phimoe", "hidden_size": 64, "intermediate_size": 96,
         "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_local_experts": 4, "router_experts": 8, "expert_first": 4,
         "num_experts_per_tok": 2, "vocab_size": 300, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-05, "router_jitter_noise": 0.01, "attention_bias": True,
         "lm_head_bias": True, "tie_word_embeddings": False}
F32 = replace(SERVE_RUN, compute_dtype="float32", param_dtype="float32",
              decode_cache_dtype="float32")


def program_params(c, seed, run=SERVE_RUN):
    """The served bf16 weights, widened to ``run``'s dtype."""
    params = make_params(build_model(arch_config(c), SERVE_RUN), seed, phimoe.leaves(c))
    return jax.tree.map(lambda x: x.astype(run.param_dtype), params)


def program_tree(c) -> dict:
    """Program leaf path -> shape and dtype of the served tree, without drawing it."""
    tree = jax.eval_shape(build_model(arch_config(c), SERVE_RUN).init, jax.random.PRNGKey(0))
    return {"/".join(str(p.key) for p in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("c", [load_config("phi3.5-moe"), SMOKE], ids=["file", "smoke"])
def test_the_leaf_map_is_the_program_tree(c):
    flat, leaves = program_tree(c), phimoe.leaves(c)
    assert sorted(flat) == sorted(leaves)
    assert all(leaves[p].program_shape == x.shape for p, x in flat.items())


def test_served_weights_weigh_11_27_gb():
    """8 layers of attention (q/k/v/o and their biases), two LayerNorms, the
    router over 16 experts and the 8 held; the padded vocabulary's embedding,
    head and head bias; the final LayerNorm."""
    d, ff, v = 4096, 6400, 32256
    layer = d * 48 * 128 + 4096 * d + 6144 + d + 4 * d + 16 * d + 8 * 3 * d * ff
    want = 8 * layer + 2 * v * d + v + 2 * d
    flat = program_tree(load_config("phi3.5-moe"))
    nbytes = sum(math.prod(x.shape) * x.dtype.itemsize for x in flat.values())
    assert nbytes == 2 * want
    assert round(nbytes / 1e9, 2) == 11.27


def test_adapter_draws_the_experts_the_program_serves():
    params = program_params(SMOKE, 11)
    w = adapter.layer_weights(SMOKE, 11, 1)
    moe = params["layers"]["moe"]
    for ref, prog in (("w1", "wg"), ("w3", "wi"), ("w2", "wo"), ("router", "router")):
        assert np.array_equal(w[ref], moe[prog][1].astype(jnp.float32)), ref
    assert np.array_equal(w["ln2_b"], params["layers"]["ln2_b"][1].astype(jnp.float32))


def test_two_held_halves_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7, each run as the program's dropless layer told what
    it holds, sum to the reference layer that holds all 8."""
    from repro.models.moe import moe_dropless

    c = dict(SMOKE, num_local_experts=8, expert_first=0)
    w = adapter.layer_weights(c, 5, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
    prog = {"router": w["router"], "wg": w["w1"], "wi": w["w3"], "wo": w["w2"]}
    with jax.default_matmul_precision("highest"):
        halves = [moe_dropless(x, jax.tree.map(lambda a: a[first: first + 4] if a.ndim == 3
                                               else a, prog),
                               8, 2, first, "sparsemixer", 0.01) for first in (0, 4)]
        whole = reference.experts(x, w, reference.frozen(c))
    assert all(float(jnp.abs(h).max()) > 0 for h in halves)
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1]), np.asarray(whole),
                               atol=2e-5, rtol=2e-5)


def test_reference_matches_the_program_prefill_in_float32():
    c = SMOKE
    model = build_model(arch_config(c), F32)
    params = program_params(c, 4, F32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 300, (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([model.prefill(params, tokens[:, : s + 1])[0][:, :300]
                          for s in (5, 23)], axis=1)
        rows = jnp.asarray([(i, s) for i in range(2) for s in (5, 23)], jnp.int32)
        got = reference.logits_at(c, 4, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want).reshape(4, -1),
                               atol=2e-4, rtol=2e-4)


def test_prefill_then_paged_decode_under_churn_matches_the_reference(monkeypatch):
    """The serving loop in float32: every served token is the reference's best
    to within float32 rounding, over requests that came and went in 3 slots."""
    monkeypatch.setattr("repro.launch.serve.SERVE_RUN", F32)
    c, mix, seed = SMOKE, dict(SMOKE_MIXES["backlog"]), 2**31 + 11
    cfg = arch_config(c)
    with jax.default_matmul_precision("highest"):
        engine = Engine(cfg, mix, program_params(c, seed, F32))
        engine.warm_up(mix["prompt_len"]["values"])
        source = Source(backlog=Backlog(mix, seed, cfg.vocab_size), depth=6)
        res = serve(engine, source, 1.0, 120.0, seed)
    done = [r for r in res["requests"].values() if len(r.tokens) == r.n_out]
    assert len(done) > mix["slots"]  # slots were freed and granted again
    gaps = check.logit_gaps(c, seed, done)
    assert gaps.size >= 80
    assert float(gaps.max()) < 1e-3


def test_backlog_driver_serves_the_moe_in_bfloat16_and_checks_it():
    """The cell's driver end to end in bfloat16. Routing is discontinuous, so
    a token whose expert choice rounding flips can land far from the float32
    reference: the float32 test above holds the arithmetic; this one holds
    that the loop serves, checks and mostly agrees."""
    ctx = smoke_ctx("gqa", "backlog", seed=2**31 + 7,
                    limits={"max_logit_gap": 0.1, "min_served_tokens": 80, "max_requests": 8})
    ctx.cfg = dict(SMOKE)
    res = backlog.run(ctx)
    v = res["verdict"]
    assert res["attempted"] > ctx.mix["slots"]
    assert res["failed"] == 0
    assert v["served_tokens_compared"] >= 80
    assert np.isfinite(v["numbers"]["max_logit_gap"]["value"])
    assert v["tokens_off_reference_argmax"] <= 0.15 * v["served_tokens_compared"]
    assert res["metrics"]["output_tok_s"] > 0


@pytest.mark.parametrize("quant", ["fp8", "fp8_experts"])
def test_fp8_controls_rank_other_tokens_first(quant):
    """The reference in fp8 (every matmul, or the experts' alone) ranks other
    tokens first where the float32 reference's own picks read zero."""
    from bench.traffic import Req

    rng = np.random.default_rng(3)
    reqs = []
    for i in range(3):
        r = Req(rid=i, due_s=0.0, prompt=rng.integers(0, 300, 40).astype(np.int32), n_out=20)
        r.tokens = list(rng.integers(0, 300, 20))
        reqs.append(r)
    tokens, rows, _ = check.pack_rows(reqs)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.logits_at(SMOKE, 5, jnp.asarray(tokens), jnp.asarray(rows)))
    assert check.logit_gaps(SMOKE, 5, reqs, chosen=ref.argmax(-1)).max() == 0.0
    assert check.logit_gaps(SMOKE, 5, reqs, quant=quant).max() > 0.1


def test_expert_counts_by_hand():
    """One layer's held-expert work at 3 live rows of 4: d 2, ff 3, 4 of 8 experts held."""
    c = {"hidden_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1,
         "intermediate_size": 3, "num_hidden_layers": 1, "num_local_experts": 4,
         "router_experts": 8, "num_experts_per_tok": 2, "vocab_size": 10}
    flops, nbytes = phimoe.expert_call(c, [5, 0, 2, 9])
    pairs = 3 * 2 * 4 / 8
    hit = 4 * (1 - (1 - 2 / 8) ** 3)  # held experts that 3 live rows reach, routed evenly
    assert flops == 2 * 3 * 2 * 3 * pairs
    assert nbytes == pytest.approx((hit * 3 * 2 * 3 + 2 * pairs * 2) * 2)  # w1, w3, w2; rows
    assert phimoe.expert_call(c, [0, 0]) == (0, 0)


def _trace_view(model, c, op_s, n=4):
    from types import SimpleNamespace

    from bench.serving import NAMES

    return SimpleNamespace(
        model=model, cfg=c, names=NAMES, peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        trace={"op_s": op_s, "module_n": {"jit_decode": n}},
        tw=SimpleNamespace(attn_calls=[np.array([5, 0, 2, 9]), np.array([6, 1, 3, 0])]))


def test_expert_roofline_reads_the_decode_programs_kernel_alone():
    """Uniform routing's least time a step, times the layers and the decode
    programs traced, over the kernel's self time under ``jit_decode`` only."""
    from bench import work

    reader = run.reader("expert_gmm_roofline.moe")
    c = {"hidden_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1,
         "intermediate_size": 3, "num_hidden_layers": 2, "num_local_experts": 4,
         "router_experts": 8, "num_experts_per_tok": 2, "vocab_size": 10}
    op_s = {"jit_decode/%held_expert_gmm.3 bf16[64,3] custom-call": 2e-9,
            "jit_decode/%held_expert_gmm.4 bf16[64,2] custom-call": 1e-9,
            "jit_prefill/%held_expert_gmm.1 bf16[512,3] custom-call": 5.0,
            "jit_decode/%fusion.7 bf16[4,2] fusion": 7.0}
    m = _trace_view(phimoe, c, op_s)
    steps = [work.roofline_s(*phimoe.expert_call(c, kv), m.peak)[0] for kv in m.tw.attn_calls]
    want = 100.0 * sum(steps) / 2 * 2 * 4 / 3e-9
    assert reader(m) == pytest.approx(want)
    assert reader(_trace_view(phimoe, c, {"jit_prefill/%held_expert_gmm.1 x": 5.0})) is None


def test_expert_roofline_reads_nothing_for_a_dense_model():
    from bench.models import dense

    reader = run.reader("expert_gmm_roofline.moe")
    m = _trace_view(dense, {"num_hidden_layers": 2}, {"jit_decode/%held_expert_gmm.3 x": 1.0})
    assert reader(m) is None
