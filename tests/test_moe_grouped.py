"""Grouped DP-local MoE dispatch vs baseline semantics (the capacity path,
deepseek-v3's routing)."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import moe as moe_lib, params as P


def _cfgs():
    cfg = base.get("deepseek-v3-671b", smoke=True)
    grouped = dc.replace(cfg, moe=dc.replace(cfg.moe, grouped_dispatch=True,
                                             n_groups=2))
    return cfg, grouped


def test_grouped_matches_baseline_modulo_capacity():
    cfg, grouped = _cfgs()
    p = P.materialize(jax.random.PRNGKey(7), moe_lib.moe_spec(cfg))
    p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model),
                          jnp.float32)
    y0, aux0 = moe_lib.moe_ffn(p, x, cfg)
    y1, aux1 = moe_lib.moe_ffn(p, x, grouped)
    assert y1.shape == x.shape
    assert np.isfinite(float(aux1["loss"])) and float(aux1["loss"]) >= 0
    # same routing, different capacity granularity: outputs close
    rel = float(jnp.linalg.norm(y1 - y0) / jnp.maximum(
        jnp.linalg.norm(y0), 1e-9))
    assert rel < 0.05, rel


def test_grouped_gradients_flow():
    _, grouped = _cfgs()
    p = P.materialize(jax.random.PRNGKey(7), moe_lib.moe_spec(grouped))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 16, grouped.d_model),
                          jnp.float32)

    def loss(p_):
        y, aux = moe_lib.moe_ffn(p_, x, grouped)
        return jnp.sum(jnp.square(y)) + aux["loss"]

    g = jax.grad(loss)(p)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(t.astype(jnp.float32)))
                      for t in jax.tree.leaves(g)))
    assert np.isfinite(float(gn)) and float(gn) > 0


def test_grouped_in_full_train_loss():
    cfg = base.get("deepseek-v3-671b", smoke=True)
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, grouped_dispatch=True,
                                         n_groups=2))
    from repro.models import transformer
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                              cfg.vocab_size)
    loss = transformer.train_loss(prm, cfg,
                                  {"tokens": toks[:, :-1],
                                   "labels": toks[:, 1:]})
    assert np.isfinite(float(loss))
