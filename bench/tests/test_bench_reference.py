"""The plain reference against the program's model at the registry's
reduced sizes on the CPU: the seeded weights bit for bit, the forward
logits, and the training loss and gradient (both in float32 here, so the
tolerances are float32 round-off)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference


def hf_config(prog):
    return {"hidden_size": prog.d_model, "intermediate_size": prog.d_ff,
            "num_hidden_layers": prog.n_layers,
            "num_attention_heads": prog.n_heads,
            "num_key_value_heads": prog.n_kv_heads,
            "head_dim": prog.head_dim or None, "qk_norm": prog.qk_norm,
            "rms_norm_eps": prog.norm_eps, "rope_theta": prog.rope_theta,
            "tie_word_embeddings": prog.tie_embeddings,
            "vocab_size": prog.vocab_size}


@pytest.fixture(scope="module", params=["smollm-135m", "qwen3-4b"])
def model(request):
    from repro.configs import base
    from repro.models import params as P, transformer
    prog = base.get(request.param, smoke=True)
    cfg = hf_config(prog)
    seed = 2**31 + 11
    prog_params = P.materialize(jax.random.PRNGKey(seed),
                                transformer.param_spec(prog))
    return prog, cfg, seed, prog_params


def test_seeded_weights_match_the_entry_point(model):
    _, cfg, seed, prog_params = model
    ref = reference.flatten(reference.init_params(cfg, seed))
    got = reference.flatten(prog_params)
    assert ref.keys() == got.keys()
    for k in ref:
        assert ref[k].dtype == got[k].dtype, k
        np.testing.assert_array_equal(np.asarray(ref[k], np.float32),
                                      np.asarray(got[k], np.float32), err_msg=str(k))


def _batch(cfg, b=2, s=24):
    toks, labels = reference.synth_batch(b, s, cfg["vocab_size"], step=3, seed=0)
    return jnp.asarray(toks), jnp.asarray(labels)


def test_forward_logits(model):
    from repro.models import transformer
    prog, cfg, _, params = model
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    toks, _ = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, _, _ = transformer.forward(p32, prog, toks)
        want = jnp.stack([reference.logits(params, cfg, t) for t in toks])
    got = np.asarray(got[..., :cfg["vocab_size"]])
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)


def test_training_loss_and_gradient(model):
    from repro.models import transformer
    prog, cfg, _, params = model
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    toks, labels = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: transformer.train_loss(p, prog, {"tokens": toks,
                                                       "labels": labels}))(p32)
        ref_loss, ref_grads = jax.jit(
            lambda p: reference.loss_and_grads(p, cfg, toks, labels))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got, want = reference.flatten(grads), reference.flatten(ref_grads)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-3, atol=2e-5, err_msg=str(k))


def test_low_precision_moves_the_result(model):
    _, cfg, _, params = model
    toks, _ = _batch(cfg)
    hi = reference.logits(params, cfg, toks[0])
    lo = reference.logits(params, cfg, toks[0], low=jnp.float8_e4m3fn)
    assert float(jnp.max(jnp.abs(hi - lo))) > 1e-3
