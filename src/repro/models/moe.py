"""Mixture-of-experts FFN: dropless routing over the experts a chip holds,
or capacity-based top-k (GShard-style dispatch/combine einsums).

Expert weights live stacked on an 'expert' axis that the sharding rules map to
the 'model' mesh axis (expert parallelism); tokens stay sharded over the DP
axes.

**Dropless** (``capacity_factor`` None; Moonlight, DeepSeek-V3's
published routing): sigmoid scores over all ``n_experts``; the top-k of
the scores plus a per-expert correction bias select the experts (the bias
carries no gradient and moves only through :func:`update_bias`); the
weights are the selected scores, normalised, times ``routed_scale``. The layer holds experts
``[0, n_held)`` (one chip's share of an expert-parallel deployment): every
(token, slot) routed to a held expert is computed, with no capacity, by a
grouped matmul (``jax.lax.ragged_dot``) over the (token, slot) pairs sorted
by expert, in O(T·k) memory. What the absent experts would add is left out;
the shared experts are computed on every chip. A sequence-wise balance loss
(``seq_aux_weight``) is returned for the train loss.

**Capacity** (a number; the other MoE configurations): softmax router,
top-k, per-chunk capacity C = ceil(chunk*k/E * cf). Token streams are
processed in fixed-size chunks (scan) so the (chunk, experts, capacity)
dispatch tensor is bounded regardless of the global batch — e.g. deepseek
train_4k: (2048, 256, 80) bf16 = 84 MB live. Overflow tokens drop; the
combine weights renormalize over the surviving experts. Aux load-balance +
router-z losses are returned for the train loss.

``moe_ffn`` returns ``(y, stats)``: ``stats["loss"]`` is the auxiliary
loss; the dropless path adds ``load`` (tokens that selected each of the
``n_experts``, for the bias update), ``routed`` ((token, slot) pairs the
held experts computed) and ``max_load`` (the largest held expert's load
over the balanced load T·k / n_experts).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.params import ParamSpec


def moe_spec(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    m = cfg.moe
    d = cfg.d_model

    def mk(shape, axes, **kw):
        if layers is not None:
            shape = (layers,) + shape
            axes = ("layers",) + axes
        return ParamSpec(shape, axes, **kw)

    e = m.held
    spec = {
        "router": mk((d, m.n_experts), ("embed", "expert"), dtype=jnp.float32),
        "w_gate": mk((e, d, m.d_expert), ("expert", "embed", "e_mlp")),
        "w_up": mk((e, d, m.d_expert), ("expert", "embed", "e_mlp")),
        "w_down": mk((e, m.d_expert, d), ("expert", "e_mlp", "embed")),
    }
    if m.bias_update_rate:
        spec["e_bias"] = mk((m.n_experts,), ("expert",), dtype=jnp.float32,
                            init="zeros")
    if m.n_shared_experts:
        ds = m.d_expert * m.n_shared_experts
        spec.update({
            "shared_gate": mk((d, ds), ("embed", "mlp")),
            "shared_up": mk((d, ds), ("embed", "mlp")),
            "shared_down": mk((ds, d), ("mlp", "embed")),
        })
    return spec


# ---------------------------------------------------------------------------
# dropless routing over the held experts
# ---------------------------------------------------------------------------

def select(router_w, bias, x, m: MoEConfig):
    """Sigmoid scores over all experts and the top-k selection.

    x: (..., D). Returns (scores (..., E) f32, top_e (..., k), top_w (..., k)
    f32): experts by score plus ``bias`` (selection only), weights from the
    scores alone, normalised and scaled."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router_w)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, top_e = jax.lax.top_k(choice, m.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return scores, top_e, top_w * m.routed_scale


def seq_balance_loss(scores, top_e, m: MoEConfig):
    """DeepSeek-V3's sequence-wise balance loss, alpha x sum_i f_i P_i per
    sequence, averaged over sequences. scores (B, S, E), top_e (B, S, k)."""
    e, k, s = m.n_experts, m.top_k, scores.shape[1]
    picked = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=-2)
    f = jnp.sum(picked, axis=1) * (e / (k * s))                     # (B, E)
    p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    return m.seq_aux_weight * jnp.mean(jnp.sum(f * p, axis=-1))


def routed_experts(p, x2d, top_e, top_w, first: int = 0):
    """The held experts' part of the layer, dropless.

    x2d (T, D); top_e, top_w (T, k) over global expert ids; the layer holds
    experts ``[first, first + held)`` (``p["w_gate"]`` etc. stacked on the
    held axis). Every (token, slot) routed to a held expert is computed:
    the pairs are sorted by expert, gathered, run through a grouped matmul
    per held expert and combined in float32. Returns (y (T, D),
    group sizes (held,))."""
    t, k = top_e.shape
    held = p["w_gate"].shape[0]
    local = top_e.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)             # held pairs first
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    # rows past the held pairs belong to no group: a grouped matmul leaves
    # them unwritten (on the TPU, whatever the buffer held), forward and in
    # the gradient of its input, so they are zeroed on both sides
    valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(valid, x2d[order // k], jnp.zeros((), x2d.dtype))
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, p["w_gate"], sizes)
        u = jax.lax.ragged_dot(xs, p["w_up"], sizes)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
        out = jax.lax.ragged_dot(h, p["w_down"], sizes)
    with jax.named_scope("moe_combine"):
        # put every pair back in (token, slot) order and weigh it
        out = jnp.where(valid, out, jnp.zeros((), out.dtype))
        back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
        pairs = out[back].reshape(t, k, -1)
        y = jnp.einsum("tkd,tk->td", pairs, top_w.astype(pairs.dtype),
                       preferred_element_type=jnp.float32)
    return y.astype(x2d.dtype), sizes


def shared_experts(p, x):
    g = jnp.einsum("...d,df->...f", x, p["shared_gate"])
    u = jnp.einsum("...d,df->...f", x, p["shared_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, p["shared_down"])


def _dropless_ffn(p, x, cfg: ModelConfig):
    m = cfg.moe
    b, s, d = x.shape
    with jax.named_scope("moe_routing"):
        scores, top_e, top_w = select(p["router"], p.get("e_bias"), x, m)
        loss = (seq_balance_loss(scores, top_e, m) if m.seq_aux_weight
                else jnp.zeros((), jnp.float32))
        load = jnp.sum(jax.nn.one_hot(top_e, m.n_experts, dtype=jnp.float32),
                       axis=(0, 1, 2))
    y, sizes = routed_experts(p, x.reshape(-1, d), top_e.reshape(-1, m.top_k),
                              top_w.reshape(-1, m.top_k))
    y = y.reshape(b, s, d)
    if m.n_shared_experts:
        y = y + shared_experts(p, x)
    balanced = b * s * m.top_k / m.n_experts
    stats = {"loss": loss, "load": load,
             "routed": jnp.sum(sizes).astype(jnp.float32),
             "max_load": jnp.max(sizes).astype(jnp.float32) / balanced}
    return y, stats


def update_bias(bias, load, rate: float):
    """Auxiliary-loss-free balancing: each expert's bias moves by ``rate``
    toward balance, down if it took more than the mean load, up if less.
    bias, load (..., E); the load is counted over the tokens this chip
    routed."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load)


# ---------------------------------------------------------------------------
# capacity-based dispatch
# ---------------------------------------------------------------------------

def _expert_ffn(p, x_d: jax.Array) -> jax.Array:
    """x_d: (E, C, D) -> (E, C, D), per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", x_d, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x_d, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x_d.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def route(router_w, x, m: MoEConfig):
    """Returns (top-k expert ids, renormalized top-k weights, aux losses)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)          # (T,k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # load-balance loss (Switch): E * sum_e f_e * p_e
    e = m.n_experts
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32), axis=0)
    prob_frac = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return top_e, top_w, aux * m.router_aux_weight + z * m.router_z_weight


def _dispatch_combine(top_e, top_w, m: MoEConfig, chunk: int):
    """Build (chunk, E, C) dispatch one-hot and combine weights."""
    e = m.n_experts
    cap = max(1, math.ceil(chunk * m.top_k / e * m.capacity_factor))
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)       # (T,k,E)
    flat = onehot.reshape(-1, e)                              # (T*k, E) row order: t*k+s
    pos = jnp.cumsum(flat, axis=0) - flat                     # slots before this one
    pos = jnp.sum(pos * flat, axis=-1).reshape(chunk, m.top_k)
    keep = pos < cap
    disp = (
        jax.nn.one_hot(top_e, e, dtype=jnp.float32)
        * keep[..., None]
    )                                                          # (T,k,E)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                            dtype=jnp.float32)[..., :cap]      # (T,k,C)
    dispatch = jnp.einsum("tke,tkc->tec", disp, pos_oh)        # (T,E,C) 0/1
    combine = jnp.einsum("tke,tkc,tk->tec", disp, pos_oh,
                         top_w.astype(jnp.float32))
    return dispatch, combine, cap


def _grouped_body(p, xc: jax.Array, cfg: ModelConfig):
    """DP-local grouped dispatch (hillclimb variant).

    xc: (T, D) one chunk. Tokens reshape to (G, T/G, D) with the group axis
    pinned to the dp mesh axes; routing, position assignment and the
    dispatch/combine einsums all carry the g axis, so their contractions are
    group-LOCAL — no cross-'data' reduction of (E, C, D) tensors. Only the
    (g, e, c, d) -> expert-sharded transition moves data (all-to-all-like),
    and the combine all-reduce is token-sized, not capacity-sized.
    """
    m = cfg.moe
    t, d = xc.shape
    g = min(m.n_groups, t)
    while t % g:
        g -= 1
    gs = t // g
    e = m.n_experts
    xg = jnp.reshape(xc, (g, gs, d))
    xg = _dp_constraint(xg)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)            # (g,t,k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    disp_frac = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    prob_frac = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(disp_frac * prob_frac) * m.router_aux_weight
    aux = aux + jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))) \
        * m.router_z_weight

    cap = max(1, math.ceil(gs * m.top_k / e * m.capacity_factor))
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)      # (g,t,k,E)
    flat = onehot.reshape(g, gs * m.top_k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                   # per-group queue
    pos = jnp.sum(pos * flat, axis=-1).reshape(g, gs, m.top_k)
    keep = pos < cap
    # NOTE (§Perf iter 2, refuted): building these one-hots in bf16 with
    # explicit dp constraints REGRESSED both terms (+54%/+34%) — the
    # constraints forced materialized reshards XLA otherwise avoided.
    # Keeping the f32 formulation that measured best (tag hc_grouped).
    disp = jax.nn.one_hot(top_e, e, dtype=jnp.float32) * keep[..., None]
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                            dtype=jnp.float32)[..., :cap]   # (g,t,k,C)
    dispatch = jnp.einsum("gtke,gtkc->gtec", disp, pos_oh)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", disp, pos_oh,
                         top_w.astype(jnp.float32))
    x_d = jnp.einsum("gtec,gtd->gecd", dispatch.astype(xc.dtype), xg)
    x_d = _gep_constraint(x_d)                              # g->dp, e->model
    gg = jnp.einsum("gecd,edf->gecf", x_d, p["w_gate"])
    uu = jnp.einsum("gecd,edf->gecf", x_d, p["w_up"])
    hh = jax.nn.silu(gg.astype(jnp.float32)).astype(x_d.dtype) * uu
    y_d = jnp.einsum("gecf,efd->gecd", hh, p["w_down"])
    y_d = _gep_constraint(y_d)
    yg = jnp.einsum("gtec,gecd->gtd", combine.astype(xc.dtype), y_d)
    return jnp.reshape(yg, (t, d)), aux


def moe_ffn(p, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, D) -> (y, stats); see the module docstring."""
    m = cfg.moe
    if m.capacity_factor is None:
        return _dropless_ffn(p, x, cfg)
    if m.held != m.n_experts:
        raise ValueError("a share of the experts needs dropless routing")
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    chunk = min(m.token_chunk, t)
    if t % chunk:
        chunk = t  # smoke shapes
    n_chunks = t // chunk
    tokens = tokens.reshape(n_chunks, chunk, d)

    def body(aux, xc):
        if m.grouped_dispatch:
            yc, aux_c = _grouped_body(p, xc, cfg)
            return aux + aux_c, yc
        top_e, top_w, aux_c = route(p["router"], xc, m)
        dispatch, combine, cap = _dispatch_combine(top_e, top_w, m, chunk)
        x_d = jnp.einsum("tec,td->ecd", dispatch.astype(xc.dtype), xc)
        x_d = _ep_constraint(x_d)
        y_d = _expert_ffn(p, x_d)
        y_d = _ep_constraint(y_d)
        yc = jnp.einsum("tec,ecd->td", combine.astype(xc.dtype), y_d)
        return aux + aux_c, yc

    if cfg.unroll_scans:
        aux = jnp.zeros((), jnp.float32)
        ys = []
        for i in range(n_chunks):
            aux, yc = body(aux, tokens[i])
            ys.append(yc)
        y = jnp.stack(ys)
    else:
        aux, y = jax.lax.scan(body, jnp.zeros((), jnp.float32), tokens)
    y = y.reshape(b, s, d)
    if m.n_shared_experts:
        y = y + shared_experts(p, x)
    return y, {"loss": aux / n_chunks}


def _ep_constraint(x_ecd):
    """Pin the expert dim to the 'model' axis (EP) when inside a mesh."""
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(x_ecd, P("model", None, None))
    except (ValueError, RuntimeError):
        return x_ecd


def _mesh_axis_names():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.get_abstract_mesh()
        return tuple(m.axis_names) if m is not None else ()
    except Exception:  # noqa: BLE001
        return ()


def _dp_constraint(x_gtd):
    """Groups over the dp axes (grouped dispatch)."""
    try:
        from jax.sharding import PartitionSpec as P
        names = _mesh_axis_names()
        dp = tuple(a for a in ("pod", "data") if a in names)
        if not dp:
            return x_gtd
        return jax.lax.with_sharding_constraint(
            x_gtd, P(dp if len(dp) > 1 else dp[0], None, None))
    except (ValueError, RuntimeError):
        return x_gtd


def _dp_constraint4(x_gtec):
    """(g,t,e,c): groups over dp, rest local (dispatch/combine tensors)."""
    try:
        from jax.sharding import PartitionSpec as P
        names = _mesh_axis_names()
        dp = tuple(a for a in ("pod", "data") if a in names)
        if not dp:
            return x_gtec
        return jax.lax.with_sharding_constraint(
            x_gtec, P(dp if len(dp) > 1 else dp[0], None, None, None))
    except (ValueError, RuntimeError):
        return x_gtec


def _gep_constraint(x_gecd):
    """(g,e,c,d): groups over dp, experts over 'model'."""
    try:
        from jax.sharding import PartitionSpec as P
        names = _mesh_axis_names()
        dp = tuple(a for a in ("pod", "data") if a in names)
        if not dp or "model" not in names:
            return x_gecd
        return jax.lax.with_sharding_constraint(
            x_gecd, P(dp if len(dp) > 1 else dp[0], "model", None, None))
    except (ValueError, RuntimeError):
        return x_gecd


def moe_ffn_reference(p, x: jax.Array, cfg: ModelConfig):
    """Per-token loop oracle with the same capacity/drop semantics (tests)."""
    import numpy as np

    m = cfg.moe
    b, s, d = x.shape
    tokens = np.asarray(x.reshape(-1, d), np.float32)
    t = tokens.shape[0]
    chunk = min(m.token_chunk, t)
    if t % chunk:
        chunk = t
    logits = tokens @ np.asarray(p["router"], np.float32)
    out = np.zeros_like(tokens)
    e = m.n_experts
    for c0 in range(0, t, chunk):
        attempts = np.zeros(e, np.int64)  # GShard positions count overflow too
        cap = max(1, math.ceil(chunk * m.top_k / e * m.capacity_factor))
        for i in range(c0, c0 + chunk):
            lg = logits[i]
            probs = np.exp(lg - lg.max())
            probs /= probs.sum()
            top = np.argsort(-probs, kind="stable")[: m.top_k]
            w = probs[top] / max(probs[top].sum(), 1e-9)
            for ee, ww in zip(top, w):
                position = attempts[ee]
                attempts[ee] += 1
                if position >= cap:
                    continue
                xi = tokens[i]
                g = xi @ np.asarray(p["w_gate"][ee], np.float32)
                u = xi @ np.asarray(p["w_up"][ee], np.float32)
                h = (g / (1 + np.exp(-g))) * u
                out[i] += ww * (h @ np.asarray(p["w_down"][ee], np.float32))
    y = out.reshape(b, s, d)
    if m.n_shared_experts:
        xs = np.asarray(x, np.float32)
        g = xs @ np.asarray(p["shared_gate"], np.float32)
        u = xs @ np.asarray(p["shared_up"], np.float32)
        h = (g / (1 + np.exp(-g))) * u
        y = y + h @ np.asarray(p["shared_down"], np.float32)
    return y
