"""Plain float32 reference of the benchmark's decoder LMs.

Written from the published architectures (Llama layout: RMSNorm, rotary
embeddings on half-split heads, grouped-query attention, SwiGLU MLP; Qwen3
adds an RMSNorm on each query and key head), in straightforward
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no kernels,
no cache, no chunking. It imports nothing of the program under test.

Configurations are the Hugging Face ``config.json`` keys of
``bench/configs/*.json``. Weights follow the benchmark's seeded
convention (``init_params``), the one the training entry point also uses:
leaves in sorted path order, one key each from ``split(PRNGKey(seed))``;
matrices truncated-normal(-2, 2) / sqrt(fan-in over all but the last
non-layer axis), the embedding normal x 0.02, norm scales one. Matrices are
stored in bfloat16 and norm scales in float32, as the configurations run;
the reference computes in float32 from those values.

``matmul_dtype`` puts the reference in a lower precision for the control:
every matrix-product operand is rounded to that type first.
"""
from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = dict
NEG = -1e30


# ---------------------------------------------------------------------------
# shapes and the seeded weight convention
# ---------------------------------------------------------------------------

def head_dim(cfg: Mapping) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def padded_vocab(cfg: Mapping) -> int:
    """Embedding rows: the vocabulary rounded up to a multiple of 256."""
    return -(-cfg["vocab_size"] // 256) * 256


def _leaf_specs(cfg: Mapping) -> dict:
    """{path: (shape, init, dtype, fan_in)}; the layer axis leads."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, n, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    ff, v = cfg["intermediate_size"], padded_vocab(cfg)
    bf, f32 = jnp.bfloat16, jnp.float32
    spec = {
        ("blocks", "attn", "wk"): ((L, d, n, hd), "normal", bf, d * n),
        ("blocks", "attn", "wo"): ((L, h, hd, d), "normal", bf, h * hd),
        ("blocks", "attn", "wq"): ((L, d, h, hd), "normal", bf, d * h),
        ("blocks", "attn", "wv"): ((L, d, n, hd), "normal", bf, d * n),
        ("blocks", "ln1", "scale"): ((L, d), "ones", f32, 0),
        ("blocks", "ln2", "scale"): ((L, d), "ones", f32, 0),
        ("blocks", "mlp", "w_down"): ((L, ff, d), "normal", bf, ff),
        ("blocks", "mlp", "w_gate"): ((L, d, ff), "normal", bf, d),
        ("blocks", "mlp", "w_up"): ((L, d, ff), "normal", bf, d),
        ("embed", "embedding"): ((v, d), "embed", bf, 0),
        ("final_norm", "scale"): ((d,), "ones", f32, 0),
    }
    if cfg.get("qk_norm"):
        spec[("blocks", "attn", "k_norm")] = ((L, hd), "ones", f32, 0)
        spec[("blocks", "attn", "q_norm")] = ((L, hd), "ones", f32, 0)
    if not cfg["tie_word_embeddings"]:
        spec[("embed", "unembed")] = ((d, v), "normal", bf, d)
    return dict(sorted(spec.items()))


def _nest(flat: Mapping[tuple, Any]) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val
    return out


def flatten(tree: Mapping, prefix: tuple = ()) -> dict:
    """{path tuple: leaf} of a nested dict."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, Mapping):
            out.update(flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def init_params(cfg: Mapping, seed: int) -> Params:
    """The seeded weights, in their stored dtypes, in one jitted call."""
    specs = _leaf_specs(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for k, (path, (shape, init, dtype, fan_in)) in zip(keys, specs.items()):
            if init == "ones":
                out[path] = jnp.ones(shape, dtype)
            elif init == "embed":
                out[path] = (jax.random.normal(k, shape, jnp.float32) * 0.02
                             ).astype(dtype)
            else:
                x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                out[path] = (x * (1.0 / math.sqrt(fan_in))).astype(dtype)
        return _nest(out)

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _mm(spec: str, a, b, low: Optional[Any]):
    if low is not None:
        a = a.astype(low).astype(jnp.float32)
        b = b.astype(low).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Rotary embedding on the two halves of each head (Llama layout)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: Mapping, low, x, p, positions):
    """One decoder layer on one sequence: x (S, d) float32."""
    eps = cfg["rms_norm_eps"]
    h, n = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = x.shape[0]
    xn = _rmsnorm(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = _mm("sd,dhk->shk", xn, a["wq"], low)
    k = _mm("sd,dnk->snk", xn, a["wk"], low)
    v = _mm("sd,dnk->snk", xn, a["wv"], low)
    if cfg.get("qk_norm"):
        q = _rmsnorm(q, a["q_norm"], eps)
        k = _rmsnorm(k, a["k_norm"], eps)
    q = _rope(q, positions, cfg["rope_theta"])
    k = _rope(k, positions, cfg["rope_theta"])
    g = h // n
    q = q.reshape(s, n, g, -1)
    scores = _mm("sngk,tnk->ngst", q, k, low) / math.sqrt(q.shape[-1])
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal, scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("ngst,tnk->sngk", probs, v, low).reshape(s, h, -1)
    x = x + _mm("shk,hkd->sd", o, a["wo"], low)
    xn = _rmsnorm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    gate = _mm("sd,df->sf", xn, m["w_gate"], low)
    up = _mm("sd,df->sf", xn, m["w_up"], low)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"], low)


def logits(params: Params, cfg: Mapping, tokens, *, low=None):
    """Logits (S, vocab) of one sequence, float32. Each layer's weights are
    widened to float32 inside the layer loop, so a model whose float32
    copy would not fit the device still runs."""
    f32 = lambda t: t.astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    emb = f32(params["embed"]["embedding"])
    x = emb[tokens]
    body = jax.checkpoint(
        lambda x, p: _layer(cfg, low, x, jax.tree.map(f32, p), positions))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, params["blocks"])
    x = _rmsnorm(x, f32(params["final_norm"]["scale"]), cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        out = _mm("sd,vd->sv", x, emb, low)
    else:
        out = _mm("sd,dv->sv", x, f32(params["embed"]["unembed"]), low)
    return out[:, :cfg["vocab_size"]]


# ---------------------------------------------------------------------------
# training: mean token cross-entropy and AdamW, as the configurations state
# ---------------------------------------------------------------------------

def _row_nll_sum(params, cfg, tokens, labels, low):
    lg = logits(params, cfg, tokens, low=low)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss_and_grads(params, cfg, tokens, labels, *, low=None):
    """Mean cross-entropy over every token of the batch and its float32
    gradient, one row at a time so that a long batch fits."""
    b, s = tokens.shape
    # differentiate with respect to float32 values: a gradient taken with
    # respect to bfloat16 leaves would come back rounded to bfloat16
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    vg = jax.value_and_grad(lambda p, t, l: _row_nll_sum(p, cfg, t, l, low))
    zero = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), params)

    def row(carry, tl):
        tot, g = carry
        val, gr = vg(params, *tl)
        return (tot + val, jax.tree.map(jnp.add, g, gr)), None

    (tot, g), _ = jax.lax.scan(row, (jnp.zeros((), jnp.float32), zero),
                               (tokens, labels))
    n = float(b * s)
    return tot / n, jax.tree.map(lambda t: t / n, g)


def lr_at(count, opt: Mapping):
    """Linear warm-up to the peak, then cosine to ``floor`` x peak."""
    step = count.astype(jnp.float32)
    warm = opt["lr_peak"] * step / max(opt["lr_warmup"], 1)
    t = jnp.clip((step - opt["lr_warmup"])
                 / max(opt["lr_total"] - opt["lr_warmup"], 1), 0.0, 1.0)
    floor = opt["lr_floor"]
    cos = opt["lr_peak"] * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < opt["lr_warmup"], warm, cos)


def adamw(params, mu, nu, grads, count, opt: Mapping):
    """One AdamW update (decoupled weight decay, global-norm clipping).
    Math in float32; parameters keep their stored dtype, moments are stored
    in ``opt['moment_dtype']``. Returns (params, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    lr = lr_at(count, opt)
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1.0 - b1 ** (count + 1).astype(jnp.float32)
    c2 = 1.0 - b2 ** (count + 1).astype(jnp.float32)
    mdt = jnp.dtype(opt["moment_dtype"])

    def leaf(p, g, m, v):
        g = g * clip
        m = m.astype(jnp.float32) * b1 + g * (1 - b1)
        v = jnp.maximum(v.astype(jnp.float32), 0.0) * b2 + g * g * (1 - b2)
        upd = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        p32 = p.astype(jnp.float32)
        upd = upd + opt["weight_decay"] * p32
        return (p32 - lr * upd).astype(p.dtype), m.astype(mdt), v.astype(mdt), g

    out = jax.tree.map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def train_steps(cfg: Mapping, opt: Mapping, seed: int, batches, *,
                low=None) -> dict:
    """Follow the first ``len(batches)`` steps from the seeded weights.

    Returns the losses, the clipped first gradient (float32) and the
    parameters after one and after all updates (stored dtypes)."""
    params = init_params(cfg, seed)
    mdt = jnp.dtype(opt["moment_dtype"])
    mu = jax.tree.map(lambda t: jnp.zeros(t.shape, mdt), params)
    nu = jax.tree.map(lambda t: jnp.zeros(t.shape, mdt), params)

    @jax.jit
    def step(params, mu, nu, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, grads = loss_and_grads(params, cfg, tokens, labels, low=low)
            p, m, v, g = adamw(params, mu, nu, grads, count, opt)
        return loss, p, m, v, g

    out = {"losses": []}
    for i, (tokens, labels) in enumerate(batches):
        loss, params, mu, nu, g = step(params, mu, nu, jnp.int32(i),
                                       jnp.asarray(tokens), jnp.asarray(labels))
        out["losses"].append(float(loss))
        if i == 0:
            out["grad1"], out["params1"] = g, params
        del g
    out["params_last"] = params
    return out


# ---------------------------------------------------------------------------
# data: the training entry point's synthetic batches
# ---------------------------------------------------------------------------

def synth_batch(batch: int, seq_len: int, vocab: int, step: int, seed: int = 0):
    """Tokens and next-token labels of one step: squared uniforms scaled to
    the vocabulary (a Zipf-like skew), from ``default_rng(seed*1000003+step)``."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    u = rng.random((batch, seq_len + 1))
    toks = (u * u * vocab).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
