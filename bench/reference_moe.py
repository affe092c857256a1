"""Plain float32 reference of the benchmark's DeepSeek-V3-layout MoE LMs
(Moonlight-16B-A3B), as one chip's share of an expert-parallel deployment.

Written from the published architecture (Hugging Face ``deepseek_v3``
modelling): RMSNorm; MLA with a full-rank query (``q_lora_rank`` null) or a
low-rank one, a shared 512-d latent for K/V and one shared rope key, rotary
embeddings on half-split heads; ``first_k_dense_replace`` SwiGLU layers, then
MoE layers: sigmoid (or softmax) scores over all ``router_experts``, the
top-k of scores plus the correction bias select, the weights are the
selected scores, normalised, times ``routed_scaling_factor``; the held
experts ``[0, n_routed_experts)`` give their part and the shared experts
theirs. DeepSeek-V3's sequence-wise balance loss joins the mean token
cross-entropy; the bias moves by +-gamma against each expert's load after
each step, with no gradient and no weight decay. Straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: every held
expert runs on every token and is weighed by its combine weight (zero where
not selected); attention is computed in blocks of queries so that an 8k
sequence fits. It imports nothing of the program under test.

The weight convention, AdamW, the learning-rate schedule and the data are
``reference.py``'s (imported): leaves in sorted path order, one key each
from ``split(PRNGKey(seed))``, matrices truncated-normal(-2, 2) /
sqrt(fan-in over all but the last non-layer axis), the embedding normal x
0.02, norm scales one, the correction bias zero.

Configurations are the keys of ``bench/configs/<name>.json``:
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` are the
share held here, ``published.n_routed_experts`` the router's width, and
``assumed.bias_update_speed`` / ``assumed.seq_aux_alpha`` gamma and alpha.
"""
from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference

NEG = -1e30
Q_BLOCK = 1024          # queries per attention block


# ---------------------------------------------------------------------------
# shapes and the seeded weights
# ---------------------------------------------------------------------------

def router_experts(cfg: Mapping) -> int:
    return int(cfg["published"]["n_routed_experts"])


def _attn_specs(cfg: Mapping, n: int) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kv, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    bf, f32 = jnp.bfloat16, jnp.float32
    spec = {
        ("kv_norm",): ((n, kv), "ones", f32, 0),
        ("wk_b",): ((n, kv, h, nope), "normal", bf, kv * h),
        ("wkv_a",): ((n, d, kv + rope), "normal", bf, d),
        ("wo",): ((n, h, vd, d), "normal", bf, h * vd),
        ("wv_b",): ((n, kv, h, vd), "normal", bf, kv * h),
    }
    if ql:
        spec[("q_norm",)] = ((n, ql), "ones", f32, 0)
        spec[("wq_a",)] = ((n, d, ql), "normal", bf, d)
        spec[("wq_b",)] = ((n, ql, h, nope + rope), "normal", bf, ql * h)
    else:
        spec[("wq",)] = ((n, d, h, nope + rope), "normal", bf, d * h)
    return spec


def _leaf_specs(cfg: Mapping) -> dict:
    """{path: (shape, init, dtype, fan_in)}; the layer axis leads."""
    d, v = cfg["hidden_size"], reference.padded_vocab(cfg)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    e, held = router_experts(cfg), cfg["n_routed_experts"]
    f, ff = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    bf, f32 = jnp.bfloat16, jnp.float32
    spec: dict = {
        ("embed", "embedding"): ((v, d), "embed", bf, 0),
        ("final_norm", "scale"): ((d,), "ones", f32, 0),
    }
    if not cfg["tie_word_embeddings"]:
        spec[("embed", "unembed")] = ((d, v), "normal", bf, d)
    for group, n in (("dense_blocks", n_dense), ("moe_blocks", n_moe)):
        if not n:
            continue
        for k, s in _attn_specs(cfg, n).items():
            spec[(group, "attn") + k] = s
        spec[(group, "ln1", "scale")] = ((n, d), "ones", f32, 0)
        spec[(group, "ln2", "scale")] = ((n, d), "ones", f32, 0)
    if n_dense:
        spec[("dense_blocks", "mlp", "w_down")] = ((n_dense, ff, d), "normal", bf, ff)
        spec[("dense_blocks", "mlp", "w_gate")] = ((n_dense, d, ff), "normal", bf, d)
        spec[("dense_blocks", "mlp", "w_up")] = ((n_dense, d, ff), "normal", bf, d)
    moe = {
        ("e_bias",): ((n_moe, e), "zeros", f32, 0),
        ("router",): ((n_moe, d, e), "normal", f32, d),
        ("shared_down",): ((n_moe, fs, d), "normal", bf, fs),
        ("shared_gate",): ((n_moe, d, fs), "normal", bf, d),
        ("shared_up",): ((n_moe, d, fs), "normal", bf, d),
        # the program's convention: all but the last non-layer axis, so a
        # stacked expert kernel's fan-in counts the experts held
        ("w_down",): ((n_moe, held, f, d), "normal", bf, held * f),
        ("w_gate",): ((n_moe, held, d, f), "normal", bf, held * d),
        ("w_up",): ((n_moe, held, d, f), "normal", bf, held * d),
    }
    for k, s in moe.items():
        spec[("moe_blocks", "moe") + k] = s
    return dict(sorted(spec.items()))


def init_params(cfg: Mapping, seed: int) -> dict:
    """The seeded weights, in their stored dtypes, in one jitted call
    (``reference.init_params``'s convention over this layout's leaves)."""
    specs = _leaf_specs(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for k, (path, (shape, init, dtype, fan_in)) in zip(keys, specs.items()):
            if init == "ones":
                out[path] = jnp.ones(shape, dtype)
            elif init == "zeros":
                out[path] = jnp.zeros(shape, dtype)
            elif init == "embed":
                out[path] = (jax.random.normal(k, shape, jnp.float32) * 0.02
                             ).astype(dtype)
            else:
                x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                out[path] = (x * (1.0 / math.sqrt(fan_in))).astype(dtype)
        return reference._nest(out)

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward pass of one sequence
# ---------------------------------------------------------------------------

_mm = reference._mm
_rmsnorm = reference._rmsnorm
_rope = reference._rope


def _attention(cfg: Mapping, low, xn, a, positions):
    """MLA over one sequence, xn (S, d) float32, in blocks of queries."""
    nope, kvr = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    s = xn.shape[0]
    if cfg["q_lora_rank"]:
        ql = _rmsnorm(_mm("sd,dl->sl", xn, a["wq_a"], low), a["q_norm"], eps)
        q = _mm("sl,lhk->shk", ql, a["wq_b"], low)
    else:
        q = _mm("sd,dhk->shk", xn, a["wq"], low)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, theta)
    kv = _mm("sd,dl->sl", xn, a["wkv_a"], low)
    c = _rmsnorm(kv[:, :kvr], a["kv_norm"], eps)
    k_rope = _rope(kv[:, None, kvr:], positions, theta)[:, 0]      # (S, r)
    k_nope = _mm("sl,lhk->shk", c, a["wk_b"], low)
    v = _mm("sl,lhk->shk", c, a["wv_b"], low)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qb = min(Q_BLOCK, s)
    assert s % qb == 0

    @jax.checkpoint
    def block(args):
        qn, qr, pos = args
        sc = (_mm("qhk,thk->hqt", qn, k_nope, low)
              + _mm("qhk,tk->hqt", qr, k_rope, low)) * scale
        sc = jnp.where(pos[:, None] >= positions[None, :], sc, NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("hqt,thk->qhk", pr, v, low)

    split = lambda t: t.reshape((s // qb, qb) + t.shape[1:])
    o = jax.lax.map(block, (split(q_nope), split(q_rope), split(positions)))
    o = o.reshape((s,) + o.shape[2:])
    return _mm("shk,hkd->sd", o, a["wo"], low)


def _swiglu(x, w_gate, w_up, w_down, low):
    g = _mm("sd,df->sf", x, w_gate, low)
    u = _mm("sd,df->sf", x, w_up, low)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, w_down, low)


def _moe(cfg: Mapping, low, x, m):
    """One MoE layer on one sequence: (y, balance loss, per-expert load,
    top-k experts)."""
    e, k = router_experts(cfg), cfg["num_experts_per_tok"]
    logits = _mm("sd,de->se", x, m["router"], low)
    scores = (jax.nn.sigmoid(logits) if cfg["scoring_func"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, top_e = jax.lax.top_k(scores + m["e_bias"], k)
    w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    picked = jax.nn.one_hot(top_e, e, dtype=jnp.float32)          # (S, k, E)
    held = cfg["n_routed_experts"]
    combine = jnp.einsum("ske,sk->se", picked, w)[:, :held]
    g = _mm("sd,edf->sef", x, m["w_gate"], low)
    u = _mm("sd,edf->sef", x, m["w_up"], low)
    out = _mm("sef,efd->sed", jax.nn.silu(g) * u, m["w_down"], low)
    y = jnp.einsum("sed,se->sd", out, combine)
    y = y + _swiglu(x, m["shared_gate"], m["shared_up"], m["shared_down"], low)
    s = x.shape[0]
    f = jnp.sum(picked, axis=(0, 1)) * (e / (k * s))
    p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=0)
    bal = cfg["assumed"]["seq_aux_alpha"] * jnp.sum(f * p)
    return y, bal, jnp.sum(picked, axis=(0, 1)), top_e


def forward(params, cfg: Mapping, tokens, *, low=None):
    """Logits (S, vocab) float32 of one sequence, the summed balance loss,
    the per-layer expert loads (n_moe, E) and top-k experts (n_moe, S, k).
    Each layer's weights are widened to float32 inside its own
    ``jax.checkpoint``."""
    f32 = lambda t: t.astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = f32(params["embed"]["embedding"])[tokens]

    def dense(x, p):
        p = jax.tree.map(f32, p)
        x = x + _attention(cfg, low, _rmsnorm(x, p["ln1"]["scale"], eps),
                           p["attn"], positions)
        xn = _rmsnorm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        return x + _swiglu(xn, m["w_gate"], m["w_up"], m["w_down"], low), None

    def moe(x, p):
        p = jax.tree.map(f32, p)
        x = x + _attention(cfg, low, _rmsnorm(x, p["ln1"]["scale"], eps),
                           p["attn"], positions)
        y, bal, load, top_e = _moe(cfg, low, _rmsnorm(x, p["ln2"]["scale"], eps),
                                   p["moe"])
        return x + y, (bal, load, top_e)

    if "dense_blocks" in params:
        x, _ = jax.lax.scan(jax.checkpoint(dense), x, params["dense_blocks"])
    x, (bal, load, top_e) = jax.lax.scan(jax.checkpoint(moe), x,
                                         params["moe_blocks"])
    x = _rmsnorm(x, f32(params["final_norm"]["scale"]), eps)
    if cfg["tie_word_embeddings"]:
        lg = _mm("sd,vd->sv", x, f32(params["embed"]["embedding"]), low)
    else:
        lg = _mm("sd,dv->sv", x, f32(params["embed"]["unembed"]), low)
    return lg[:, :cfg["vocab_size"]], jnp.sum(bal), load, top_e


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW and the bias update
# ---------------------------------------------------------------------------

def loss_and_grads(params, cfg: Mapping, tokens, labels, *, low=None):
    """Mean token cross-entropy plus the balance loss averaged over the
    sequences, its float32 gradient, the expert loads summed over the
    batch and each sequence's top-k experts; one sequence at a time."""
    b, s = tokens.shape
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)

    def row_loss(p, t, lab):
        lg, bal, load, top_e = forward(p, cfg, t, low=low)
        nll = jnp.sum(jax.nn.logsumexp(lg, axis=-1)
                      - jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0])
        return nll / (b * s) + bal / b, (load, top_e)

    vg = jax.value_and_grad(row_loss, has_aux=True)
    zero = jax.tree.map(jnp.zeros_like, params)

    def row(carry, tl):
        tot, g, load = carry
        (val, (ld, top_e)), gr = vg(params, *tl)
        return (tot + val, jax.tree.map(jnp.add, g, gr), load + ld), top_e

    n_moe = params["moe_blocks"]["moe"]["e_bias"].shape
    init = (jnp.zeros((), jnp.float32), zero, jnp.zeros(n_moe, jnp.float32))
    (tot, g, load), top_e = jax.lax.scan(row, init, (tokens, labels))
    return tot, g, load, top_e


def _split_bias(tree):
    moe = dict(tree["moe_blocks"]["moe"])
    bias = moe.pop("e_bias")
    return dict(tree, moe_blocks=dict(tree["moe_blocks"], moe=moe)), bias


def _put_bias(tree, bias):
    return dict(tree, moe_blocks=dict(
        tree["moe_blocks"], moe=dict(tree["moe_blocks"]["moe"], e_bias=bias)))


def _norms(tree) -> dict:
    return {k: jnp.linalg.norm(v.astype(jnp.float32).reshape(-1))
            for k, v in reference.flatten(tree).items()}


def train_steps(cfg: Mapping, opt: Mapping, seed: int, batches, *,
                low=None) -> dict:
    """Follow the first ``len(batches)`` steps from the seeded weights.

    Returns the losses, the per-leaf norms of the clipped first gradient,
    the per-leaf norms of the parameters' change from after the first
    update to after the last, each layer's top-k experts at the first
    step (n_moe, batch, S, k) and the router correction bias after the
    last update (n_moe, n_experts)."""
    gamma = cfg["assumed"]["bias_update_speed"]
    params = init_params(cfg, seed)
    mdt = jnp.dtype(opt["moment_dtype"])
    mu = jax.tree.map(lambda t: jnp.zeros(t.shape, mdt), params)
    nu = jax.tree.map(lambda t: jnp.zeros(t.shape, mdt), params)

    @jax.jit
    def step(params, mu, nu, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads, load, top_e = loss_and_grads(params, cfg, tokens,
                                                      labels, low=low)
            p, bias = _split_bias(params)
            g, _ = _split_bias(grads)
            m, m_bias = _split_bias(mu)
            v, v_bias = _split_bias(nu)
            p, m, v, g = reference.adamw(p, m, v, g, count, opt)
            mean = jnp.mean(load, axis=-1, keepdims=True)
            bias = bias + gamma * jnp.sign(mean - load)
            norms = _norms(_put_bias(g, jnp.zeros_like(bias)))
        return (loss, _put_bias(p, bias), _put_bias(m, m_bias),
                _put_bias(v, v_bias), norms, top_e)

    out: dict = {"losses": []}
    first = None
    for i, (tokens, labels) in enumerate(batches):
        loss, params, mu, nu, norms, top_e = step(
            params, mu, nu, jnp.int32(i), jnp.asarray(tokens),
            jnp.asarray(labels))
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = {k: float(v) for k, v in norms.items()}
            out["top_e"] = np.asarray(top_e).swapaxes(0, 1)
            first = params
        del norms, top_e
    last = reference.flatten(params)
    out["bias"] = np.asarray(last[("moe_blocks", "moe", "e_bias")])
    out["update"] = {k: float(jnp.linalg.norm(
        (last[k].astype(jnp.float32) - v.astype(jnp.float32)).reshape(-1)))
        for k, v in reference.flatten(first).items()}
    return out
