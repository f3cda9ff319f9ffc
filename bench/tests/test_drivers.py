"""Each driver end to end at smoke widths on the CPU: counts and the reference check.

The timed path here is the real one (prefill, pack into pages, paged
decode under slot churn with pages reused after retirement), at smoke
widths, with Pallas in interpret mode.
"""
import numpy as np
import pytest

from bench.drivers import backlog, open_loop
from bench.models import dense
from bench.serving import Engine, Source, make_params_for, serve
from bench.traffic import Backlog, load_mix, spread_order
from bench.traffic import open_loop as schedule
from bench.weights import arch_config
from repro.launch.serve import SERVE_RUN
from repro.models import build_model


@pytest.mark.parametrize("config", ["gqa", "mha"])
def test_open_loop_serves_every_due_request_and_agrees_with_the_reference(ctx_factory, config):
    ctx = ctx_factory(config, "open_loop", seed=2**31 + 5)
    res = open_loop.run(ctx)
    due = schedule(ctx.mix, ctx.seed, ctx.seconds, ctx.cfg["vocab_size"])
    assert res["attempted"] == len(due) == 12
    assert res["failed"] == 0
    assert res["verdict"]["correct"], res["verdict"]
    assert res["verdict"]["numbers"]["max_logit_gap"]["value"] <= ctx.limits["max_logit_gap"]
    assert res["verdict"]["served_tokens_compared"] >= ctx.limits["min_served_tokens"]
    m = res["metrics"]
    assert m["ttft_p95_ms"] > 0 and m["itl_p95_ms"] > 0 and m["setup_s"] > 0
    # churn: more requests than slots, so pages were freed and granted again
    assert res["summary"]["pool_high_water"] <= res["summary"]["pool_pages"]


@pytest.mark.parametrize("config", ["gqa", "mha"])
def test_backlog_keeps_the_slots_full_and_agrees_with_the_reference(ctx_factory, config):
    ctx = ctx_factory(config, "backlog", seed=7)
    res = backlog.run(ctx)
    assert res["attempted"] > ctx.mix["slots"]
    assert res["failed"] == 0
    assert res["verdict"]["correct"], res["verdict"]
    assert res["metrics"]["output_tok_s"] > 0


def test_steps_sent_ahead_serve_the_same_tokens(ctx_factory):
    """Sending decode steps ahead changes when the host reads tokens, not which."""
    served, closes = [], []
    for ahead in (0, 3):
        ctx = ctx_factory("mha", "backlog", seed=2**31 + 3)
        ctx.mix["dispatch_ahead"] = ahead
        cfg = arch_config(ctx.cfg)
        engine = Engine(cfg, ctx.mix, make_params_for(build_model(cfg, SERVE_RUN), ctx.seed,
                                                      dense.leaves(ctx.cfg)))
        engine.warm_up(ctx.mix["prompt_len"]["values"])
        source = Source(backlog=Backlog(ctx.mix, ctx.seed, cfg.vocab_size), depth=6)
        res = serve(engine, source, 1.0, 120.0, ctx.seed)
        served.append({rid: r.tokens for rid, r in res["requests"].items()
                       if len(r.tokens) == r.n_out})
        closes.append(res["t_close"])
    both = served[0].keys() & served[1].keys()
    assert len(both) >= 3
    assert all(served[0][rid] == served[1][rid] for rid in both)
    assert closes[0] is None and closes[1] >= 1.0


def sizes(reqs):
    return [(r.prompt_len, r.n_out, r.due_s) for r in reqs]


def test_every_seed_gets_the_same_work_in_the_same_order(ctx_factory):
    mix, vocab = ctx_factory().mix, 300
    a, b = schedule(mix, 1, 10.0, vocab), schedule(mix, 2**33 + 1, 10.0, vocab)
    assert sizes(a) == sizes(b)
    assert len(a) == len(b) and max(r.due_s for r in a + b) < 10.0
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    same = schedule(mix, 1, 10.0, vocab)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s for x, y in zip(a, same))


def test_a_backlog_gives_every_seed_the_same_sizes_spread_from_its_start():
    mix = load_mix("batch-long")
    a, b = Backlog(mix, 3, 300), Backlog(mix, 2**31 + 9, 300)
    ra, rb = [a.take() for _ in range(80)], [b.take() for _ in range(80)]
    assert sizes(ra) == sizes(rb)
    assert any((x.prompt != y.prompt).any() for x, y in zip(ra, rb))
    # the nine or so requests a window admits already mix both prompt lengths
    # and reach both halves of the output lengths
    first = ra[:8]
    median = mix["output_len"]["median"]
    assert {r.prompt_len for r in first} == set(mix["prompt_len"]["values"])
    assert min(r.n_out for r in first) < median < max(r.n_out for r in first)


@pytest.mark.parametrize("n", [1, 7, 64])
def test_spread_order_is_a_permutation(n):
    for what in ("prompt", "output", "gap"):
        assert sorted(spread_order(n, what)) == list(range(n))
