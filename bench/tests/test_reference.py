"""The plain reference and its weight adapter, on the CPU at smoke widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench.models import dense
from bench.reference import adapter
from bench.reference.model import logits_at
from bench.tests.conftest import LOOSE, SMOKE_CONFIGS
from bench.traffic import Req
from bench.weights import arch_config, make_params


@pytest.mark.parametrize("config", ["gqa", "mha", "head_dim"])
def test_adapter_draws_the_values_the_program_serves(config):
    c = SMOKE_CONFIGS[config]
    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    params = make_params(build_model(arch_config(c), SERVE_RUN), 2**32 + 9, dense.leaves(c))
    for layer in range(c["num_hidden_layers"]):
        w = adapter.layer_weights(c, 2**32 + 9, layer)
        assert np.array_equal(w["wq"], params["layers"]["attn"]["wq"][layer].astype(jnp.float32))
        assert np.array_equal(w["w_gate"], params["layers"]["mlp"]["wg"][layer].astype(jnp.float32))
        assert np.array_equal(w["ln2"], params["layers"]["ln2"][layer].astype(jnp.float32))
    top = adapter.top_weights(c, 2**32 + 9)
    v = c["vocab_size"]
    assert np.array_equal(top["embed"], params["embed"][:v].astype(jnp.float32))
    head = params["embed"].T if c["tie_word_embeddings"] else params["head"]
    assert np.array_equal(top["head"], head.astype(jnp.float32))


@pytest.mark.parametrize("config", ["gqa", "mha", "head_dim"])
def test_reference_matches_the_program_forward_in_float32(config):
    """The program's own dense forward, run in float32, gives the reference's logits."""
    from dataclasses import replace

    from repro.launch.serve import SERVE_RUN
    from repro.models import build_model

    c = SMOKE_CONFIGS[config]
    run = replace(SERVE_RUN, compute_dtype="float32", param_dtype="float32")
    model = build_model(arch_config(c), run)
    seed = 4
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          make_params(build_model(arch_config(c), SERVE_RUN), seed, dense.leaves(c)))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([model.prefill(params, tokens[:, : s + 1])[0][:, : c["vocab_size"]]
                          for s in (5, 23)], axis=1)
        rows = jnp.asarray([(i, s) for i in range(2) for s in (5, 23)], jnp.int32)
        got = logits_at(c, seed, tokens, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want).reshape(4, -1),
                               atol=2e-4, rtol=2e-4)


def test_padding_never_reaches_a_real_position():
    c = SMOKE_CONFIGS["gqa"]
    r = Req(rid=0, due_s=0.0, prompt=np.arange(1, 10, dtype=np.int32), n_out=3)
    r.tokens = [5, 6, 7]
    tokens, rows, _ = check.pack_rows([r], pad_to=32)
    longer = tokens.copy()
    longer[0, 11:] = 99
    a = logits_at(c, 1, jnp.asarray(tokens), jnp.asarray(rows))
    b = logits_at(c, 1, jnp.asarray(longer), jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("config", ["gqa", "mha"])
def test_fp8_control_fails_the_limit_the_program_passes(config):
    """The control, the reference computed in fp8, ranks other tokens first
    where float32 does not, by more than the smoke limit that the served
    bfloat16 program keeps to (`test_drivers`); the float32 reference's own
    picks read zero."""
    c = SMOKE_CONFIGS[config]
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(3):
        r = Req(rid=i, due_s=0.0, prompt=rng.integers(0, c["vocab_size"], 40).astype(np.int32),
                n_out=20)
        r.tokens = list(rng.integers(0, c["vocab_size"], 20))
        reqs.append(r)
    control = check.logit_gaps(c, 5, reqs, quant="fp8")
    tokens, rows, _ = check.pack_rows(reqs)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(logits_at(c, 5, jnp.asarray(tokens), jnp.asarray(rows)))
    own = check.logit_gaps(c, 5, reqs, chosen=ref.argmax(-1))
    assert own.max() == 0.0
    assert control.max() > LOOSE["max_logit_gap"]
