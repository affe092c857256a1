"""Paged KV cache + continuous batching (the vLLM/JetStream serving shape).

The dense ``ServingEngine`` gives every slot a full ``(max_len, ...)`` KV
stripe, so a 5-token reply pays the memory (and admission) cost of the
longest request and throughput collapses once the fixed slots fill. Here KV
memory is a shared pool of fixed-size pages per layer:

  pool leaf   (layers, num_pages, page_size, ...)   — block-indexed storage
  page_table  (max_reqs, pages_per_seq) int32       — per-request chains
  state leaf  (layers, max_reqs, ...)               — SWA rings / SSM state

A request is admitted whenever a batch row *and* enough free pages exist —
``ceil((prompt + max_new) / page_size)`` pages are reserved up front so a
mid-decode exhaustion can never corrupt a neighbour. Completion returns the
chain to the free list immediately (``free_resource``), so short requests
stop blocking long ones: no fixed slot count, no head-of-line blocking.

Lifecycle (JetStream's engine vocabulary):

  admit(req)      prefill the prompt, then insert
  _insert(...)    scatter the prefilled KV into the reserved pages and copy
                  recurrent state into the request's row
  step()          one batched decode for every row; page writes go through
                  the per-request page table
  free_resource() return pages, zero the table row

Page 0 is reserved as a scratch page: inactive rows' table entries point at
it, so their (masked, never-read) decode writes land harmlessly there.

Parity: gathering a chain back into token order and masking positions
``>= length`` to NEG_INF makes the softmax weights of garbage positions
exactly 0.0 (``exp(NEG_INF - m)`` underflows), so paged decode logits are
**bit-identical** to the dense slab's — asserted per family in
tests/test_paged_serving.py. On TPU the fused Pallas kernel
(repro/kernels/paged_attention.py) replaces the gather and matches to
float tolerance instead.

Snapshots: ``snapshot_payload`` emits the pool plus per-page dirty versions
and per-leaf ``chunk_hints`` sized to one (layer, page) slab, so the
serve_snapshot delta chunks align to pages and untouched pages frame as
zero-payload COPY ops in the PR-5 store.
"""
from __future__ import annotations

import json
from functools import partial
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import hymba as hymba_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import embed, mlp, rmsnorm, unembed
from repro.models.transformer import project_qkv
from repro.serving import engine as E
from repro.serving import kvcache
from repro.serving import prefix as prefix_lib
from repro.serving.engine import Request, make_prefill

PyTree = Any


# ---------------------------------------------------------------------------
# paged decode blocks (x: (B,1,d); kv leaves: (num_pages, page_size, ...))
# ---------------------------------------------------------------------------

def _paged_gqa_attn(p, xn, cfg: ModelConfig, kv, table, lengths, ps,
                    use_kernel=None):
    pos = lengths[:, None]                       # rope position of new token
    q, k, v = project_qkv(p, xn, cfg, pos)
    kc = attn_lib.scatter_token(kv["k"], k[:, 0], table, lengths, ps)
    vc = attn_lib.scatter_token(kv["v"], v[:, 0], table, lengths, ps)
    o = attn_lib.paged_decode_attention(q, kc, vc, table, lengths + 1,
                                        use_kernel=use_kernel)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), {"k": kc, "v": vc}


def _paged_mla_attn(p, xn, cfg: ModelConfig, kv, table, lengths, ps):
    pos = lengths[:, None]
    ckv_new, krope_new = mla_lib.mla_new_cache_entry(p, xn, cfg, pos)
    ckv = attn_lib.scatter_token(kv["ckv"], ckv_new[:, 0], table, lengths, ps)
    krope = attn_lib.scatter_token(kv["krope"], krope_new[:, 0], table,
                                   lengths, ps)
    # MLA decode is a latent-space matmul over the whole prefix — gather the
    # chain into token order and reuse the dense path (masked identically).
    o = mla_lib.mla_decode(p, xn, cfg,
                           attn_lib.gather_pages(ckv, table),
                           attn_lib.gather_pages(krope, table), lengths + 1)
    return o, {"ckv": ckv, "krope": krope}


def _paged_dense_block(p, x, cfg, kv, table, lengths, ps, use_kernel=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, kv = _paged_mla_attn(p["attn"], xn, cfg, kv, table, lengths, ps)
    else:
        a, kv = _paged_gqa_attn(p["attn"], xn, cfg, kv, table, lengths, ps,
                                use_kernel)
    x = x + a
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], xn), kv


def _paged_moe_block(p, x, cfg, kv, table, lengths, ps, use_kernel=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla is not None:
        a, kv = _paged_mla_attn(p["attn"], xn, cfg, kv, table, lengths, ps)
    else:
        a, kv = _paged_gqa_attn(p["attn"], xn, cfg, kv, table, lengths, ps,
                                use_kernel)
    x = x + a
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, _ = moe_lib.moe_ffn(p["moe"], xn, cfg)
    return x + y, kv


def _paged_hybrid_block(p, x, cfg, kv, table, ssm_state, lengths, ps,
                        use_kernel=None):
    xn = rmsnorm(p["ln1"], x, cfg.norm_eps)
    a, kv = _paged_gqa_attn(p["attn"], xn, cfg, kv, table, lengths, ps,
                            use_kernel)
    s, ssm_state = ssm_lib.ssm_decode(p["ssm"], xn, cfg, ssm_state)
    x = x + hymba_lib.fuse(p["fusion"], a, s, cfg)
    xn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + mlp(p["mlp"], xn), kv, ssm_state


def _paged_hybrid_decode(params, cfg, pool, state, table, lengths, h, ps,
                         use_kernel=None):
    """Global layers page; SWA rings and SSM state stay per-row slabs."""
    gids = set(hymba_lib.global_layer_ids(cfg))
    kinds = ["g" if i in gids else "s" for i in range(cfg.n_layers)]
    g_idx = s_idx = 0
    new_g_kv, new_s_kv, new_g_ssm, new_s_ssm = [], [], [], []
    i = 0
    while i < cfg.n_layers:
        j = i
        while j < cfg.n_layers and kinds[j] == kinds[i]:
            j += 1
        count = j - i
        is_g = kinds[i] == "g"
        idx0 = g_idx if is_g else s_idx
        pkey = "global_blocks" if is_g else "swa_blocks"
        part = lambda t: jax.tree.map(lambda a: a[idx0:idx0 + count], t)
        part_p = part(params[pkey])
        if is_g:
            part_kv = part(pool["global_kv"])
            part_ssm = part(state["ssm_global"])

            def step(carry, xs):
                p_l, kv_l, ssm_l = xs
                x, kv, ssm = _paged_hybrid_block(
                    p_l, carry, cfg, kv_l, table, ssm_l, lengths, ps,
                    use_kernel)
                return x, (kv, ssm)
        else:
            part_kv = part(state["swa_kv"])
            part_ssm = part(state["ssm_swa"])

            def step(carry, xs):
                p_l, kv_l, ssm_l = xs
                x, kv, ssm = E._hybrid_decode_block(
                    p_l, carry, cfg, kv_l, ssm_l, lengths,
                    window=cfg.swa_window)
                return x, (kv, ssm)

        h, (kv_new, ssm_new) = E._maybe_scan(
            step, h, (part_p, part_kv, part_ssm), cfg.scan_layers)
        (new_g_kv if is_g else new_s_kv).append(kv_new)
        (new_g_ssm if is_g else new_s_ssm).append(ssm_new)
        if is_g:
            g_idx += count
        else:
            s_idx += count
        i = j

    cat = lambda parts: (jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
                         if len(parts) > 1 else parts[0])
    pool = {"global_kv": cat(new_g_kv)}
    state = {"swa_kv": cat(new_s_kv), "ssm_global": cat(new_g_ssm),
             "ssm_swa": cat(new_s_ssm)}
    return h, pool, state


def make_paged_decode(cfg: ModelConfig, page_size: int, *,
                      use_kernel: Optional[bool] = None) -> Callable:
    """decode(params, pool, state, page_table, tokens (B,1), lengths (B,))
    -> (logits, pool, state, lengths+1).

    ``use_kernel`` picks the paged attention path (default: the Pallas
    kernel on TPU, the gather path elsewhere)."""
    ps = page_size

    def decode(params, pool, state, page_table, tokens, lengths):
        h = embed(params["embed"], tokens)

        if cfg.family in ("dense", "audio", "vlm"):
            body = lambda x, p, kv: _paged_dense_block(
                p, x, cfg, kv, page_table, lengths, ps, use_kernel)
            h, kv = E._scan_decode(params["blocks"], pool["kv"], h, lengths,
                                   body, use_scan=cfg.scan_layers)
            pool = {"kv": kv}

        elif cfg.family == "moe":
            m = cfg.moe
            kv = pool["kv"]
            split = lambda t: (jax.tree.map(lambda a: a[:m.first_dense], t),
                               jax.tree.map(lambda a: a[m.first_dense:], t))
            kv_d, kv_m = split(kv) if m.first_dense else (None, kv)
            if m.first_dense:
                body_d = lambda x, p, k: _paged_dense_block(
                    p, x, cfg, k, page_table, lengths, ps, use_kernel)
                h, kv_d = E._scan_decode(params["dense_blocks"], kv_d, h,
                                         lengths, body_d,
                                         use_scan=cfg.scan_layers)
            body_m = lambda x, p, k: _paged_moe_block(
                p, x, cfg, k, page_table, lengths, ps, use_kernel)
            h, kv_m = E._scan_decode(params["moe_blocks"], kv_m, h, lengths,
                                     body_m, use_scan=cfg.scan_layers)
            joined = (jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                   kv_d, kv_m) if m.first_dense else kv_m)
            pool = {"kv": joined}

        elif cfg.family == "hybrid":
            h, pool, state = _paged_hybrid_decode(
                params, cfg, pool, state, page_table, lengths, h, ps,
                use_kernel)

        elif cfg.family == "ssm":
            h, state = E._xlstm_decode(params, cfg, state, h)

        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = unembed(params["embed"], h, cfg.vocab_size)
        return logits, pool, state, lengths + 1

    return decode


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Host-side refcounted free list over page ids 1..num_pages-1 (0 is
    scratch).

    Whole chains are reserved at admission, so allocation can never fail
    mid-decode. Pages are reference-counted so one chain can back many
    requests (shared-prefix COW mapping): ``alloc`` hands out pages at
    refcount 1, ``share`` takes another reference on an already-live chain,
    and ``free`` drops one reference — a page returns to the free list only
    when its count reaches zero, so a referenced page can never be
    reclaimed out from under a reader. Over-free and foreign-page frees
    raise instead of corrupting the list (property-tested in
    tests/test_paged_serving.py and tests/test_prefix_sharing.py).
    """

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is scratch)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))   # pop() -> 1, 2, ...
        self._refs: dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 = free or out of range)."""
        return self._refs.get(page, 0)

    def refcounts(self) -> dict[int, int]:
        """Snapshot of all live page refcounts (page id -> count)."""
        return dict(self._refs)

    def alloc(self, n: int) -> Optional[list[int]]:
        """Reserve n pages at refcount 1, or None if the pool can't cover
        them."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages: list[int]) -> None:
        """Take one more reference on each page of a live chain."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} shared but not allocated")
        for p in pages:
            self._refs[p] += 1

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; reclaim pages that hit zero."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"page {p} freed but not allocated")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)

    # -- hydration ------------------------------------------------------------

    def state_dict(self) -> dict:
        """JSON-able exact state (free-list order preserved, so a restored
        allocator hands out the same pages in the same order)."""
        return {"num_pages": self.num_pages,
                "free": list(self._free),
                "refs": {str(p): c for p, c in sorted(self._refs.items())}}

    def load_state(self, state: Mapping[str, Any]) -> None:
        """Restore :meth:`state_dict` output bit-exactly."""
        if int(state["num_pages"]) != self.num_pages:
            raise ValueError(
                f"allocator size mismatch: snapshot has "
                f"{state['num_pages']} pages, this pool has {self.num_pages}")
        self._free = [int(p) for p in state["free"]]
        self._refs = {int(p): int(c) for p, c in state["refs"].items()}


# ---------------------------------------------------------------------------
# prefill-cache split + jitted insert helpers
# ---------------------------------------------------------------------------

def _split_tree(tree: dict, pool_l: dict, state_l: dict):
    """Partition a prefill cache into (pool-side, state-side) subtrees
    following the paged_cache_layout split."""
    pool, state = {}, {}
    for k, v in tree.items():
        if k in pool_l and k in state_l:          # mixed subtree
            p, s = _split_tree(v, pool_l[k], state_l[k])
            pool[k], state[k] = p, s
        elif k in pool_l:
            pool[k] = v
        else:
            state[k] = v
    return pool, state


def _insert_pages(pool, pool1, page_ids):
    """Scatter a single-request prefill cache into the reserved pages.

    pool leaf (L, NP, PS, ...) <- pool1 leaf (L, 1, max_len, ...): the first
    n*PS prompt positions, reshaped to n page slabs. Retraces per distinct
    page count n (bounded by pages_per_seq).
    """
    n = page_ids.shape[0]

    def leaf(full, one):
        layers, _, ps = full.shape[:3]
        chunk = one[:, 0, :n * ps].reshape(layers, n, ps, *one.shape[3:])
        return full.at[:, page_ids].set(chunk.astype(full.dtype))

    return jax.tree.map(leaf, pool, pool1)


def _insert_state(state, state1, row, cfg):
    return jax.tree.map(
        lambda full, one: E._set_batch_slot(full, one, row, cfg),
        state, state1)


def _insert_fused(pool, state, page_table, lengths, tokens,
                  pool1, state1, logits, row, page_ids, n_prompt, *, cfg):
    """Everything after prefill as ONE jitted computation.

    Scatters the prompt KV into the reserved pages, copies per-row state,
    writes the table row (unused slots stay on the scratch page 0), stamps
    the length, and picks the first sampled token — a single dispatch where
    the unfused path paid six plus an extra device sync. Retraces per page
    count (bounded by pages_per_seq) and per pytree structure only.
    """
    if pool1:
        pool = _insert_pages(pool, pool1, page_ids)
    if state1:
        state = _insert_state(state, state1, row, cfg)
    pps = page_table.shape[1]
    table_row = jnp.zeros((pps,), jnp.int32).at[:page_ids.shape[0]].set(
        page_ids)
    page_table = page_table.at[row].set(table_row)
    lengths = lengths.at[row].set(n_prompt)
    nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
    tokens = tokens.at[row, 0].set(nxt)
    return pool, state, page_table, lengths, tokens, nxt


def _insert_suffix_fused(pool, page_table, lengths, tokens,
                         kv1, logits, row, shared_ids, new_ids, n_prompt,
                         *, page_size):
    """Shared-prefix admit tail as ONE jitted computation.

    The prefix chain is already resident (refcount-shared, read-only), so
    only the suffix KV — computed by the continuation prefill from the
    first divergent token — is scattered, into the freshly allocated
    ``new_ids`` pages. The table row maps shared chain + new pages; the
    shared pages are never written, which is the copy-on-write invariant.
    The suffix KV arrives at the canonical padded width, which may be
    narrower (pad to the page budget, the new pages also cover decode
    slots) or wider (slice; the tail is never-attended pad junk) than
    ``n_new * page_size``. Retraces per (shared, new) page-count pair,
    bounded by pages_per_seq.
    """
    n_new = new_ids.shape[0]

    def leaf(full, one):
        layers = one.shape[0]
        want = n_new * page_size
        chunk = one[:, 0, :want]
        pad = want - chunk.shape[1]
        if pad > 0:
            chunk = jnp.pad(chunk,
                            [(0, 0), (0, pad)] + [(0, 0)] * (chunk.ndim - 2))
        chunk = chunk.reshape(layers, n_new, page_size, *one.shape[3:])
        return full.at[:, new_ids].set(chunk.astype(full.dtype))

    pool = jax.tree.map(leaf, pool, kv1)
    pps = page_table.shape[1]
    chain = jnp.concatenate([shared_ids, new_ids])
    table_row = jnp.zeros((pps,), jnp.int32).at[:chain.shape[0]].set(chain)
    page_table = page_table.at[row].set(table_row)
    lengths = lengths.at[row].set(n_prompt)
    nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
    tokens = tokens.at[row, 0].set(nxt)
    return pool, page_table, lengths, tokens, nxt


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------

class PagedServingEngine:
    """Continuous batching over a paged KV pool (drop-in for ServingEngine).

    ``num_pages`` x ``page_size`` tokens of KV storage are shared by up to
    ``max_reqs`` concurrent rows; admission needs one free row plus the
    request's full page budget. Decode proceeds while new requests prefill
    into free pages between steps, and completed chains are reclaimed
    immediately. ``use_kernel`` picks the decode attention path (default:
    the Pallas paged kernel on TPU, the gather path elsewhere).
    """

    def __init__(self, cfg: ModelConfig, params, *, num_pages: int = 65,
                 page_size: int = 16, max_reqs: int = 8,
                 prompt_len: int = 64, max_len: int = 256,
                 use_kernel: Optional[bool] = None) -> None:
        if max_len % page_size:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={page_size}")
        self.cfg = cfg
        self.params = params
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_reqs = max_reqs
        self.prompt_len = prompt_len
        self.max_len = max_len
        self.pages_per_seq = max_len // page_size

        self._pool_layout, self._state_layout = kvcache.paged_cache_layout(
            cfg, num_pages, page_size, max_reqs, max_len)
        self.pool, self.state = kvcache.init_paged_cache(
            cfg, num_pages, page_size, max_reqs, max_len)
        self.page_table = jnp.zeros((max_reqs, self.pages_per_seq), jnp.int32)
        self.lengths = jnp.zeros((max_reqs,), jnp.int32)
        self.tokens = jnp.zeros((max_reqs, 1), jnp.int32)
        self.active: list[Optional[Request]] = [None] * max_reqs
        self.allocator = PageAllocator(num_pages)
        self._chains: list[list[int]] = [[] for _ in range(max_reqs)]
        self._len_host = np.zeros(max_reqs, np.int64)   # device-sync-free
        self.prefix = prefix_lib.PrefixCache()
        self.prefill_tokens = 0    # tokens actually run through prefill
        self.shared_tokens = 0     # prompt tokens served from shared pages

        _dec = make_paged_decode(cfg, page_size, use_kernel=use_kernel)

        def _step(params, pool, state, table, tokens, lengths):
            logits, pool, state, lengths = _dec(params, pool, state, table,
                                                tokens, lengths)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, nxt[:, None], pool, state, lengths

        self._decode = jax.jit(_step)
        self._prefill_one = jax.jit(make_prefill(cfg, max_len,
                                                 last_only=True))
        # attention families prefill every prompt right-padded to one
        # canonical width (prompt_len): XLA kernel rounding is
        # shape-dependent, so a single compiled shape is what makes the
        # prefix KV a register_prefix writes bitwise equal to the KV an
        # unshared admit of the same tokens would write — the ground of
        # the sharing-parity guarantee (and one prefill trace instead of
        # one per prompt length). Recurrent families (hybrid/ssm) keep
        # exact-length prefill: padding tokens would advance their per-row
        # state past the real prompt.
        self._pad_prompts = cfg.family in prefix_lib.SHAREABLE_FAMILIES
        self._insert_fused = jax.jit(partial(_insert_fused, cfg=cfg))
        self._insert_suffix = jax.jit(
            partial(_insert_suffix_fused, page_size=page_size))
        self._register_insert = jax.jit(_insert_pages)
        self._cont_prefill = None    # built on first shared admit
        self._clear_row = jax.jit(
            lambda table, lengths, row: (table.at[row].set(0),
                                         lengths.at[row].set(0)))

        self._state_version = 0
        self._page_versions = np.zeros(num_pages, np.int64)
        self._chunk_hints = {
            jax.tree_util.keystr(path):
                int(np.prod(leaf.shape[2:])) * leaf.dtype.itemsize
            for path, leaf in
            jax.tree_util.tree_flatten_with_path({"pool": self.pool})[0]}

    # -- lifecycle ----------------------------------------------------------

    def admit(self, req: Request) -> bool:
        """Prefill + insert; False when no row or not enough free pages.

        When the prompt starts with a registered prefix, the shared chain
        is mapped read-only into the row's page table (refcount +1 per
        page) and only the divergent suffix is prefilled into fresh pages
        — prefill cost drops from the whole prompt to the suffix.
        """
        row = next((i for i, a in enumerate(self.active) if a is None), None)
        if row is None:
            return False
        prompt = E._checked_prompt(req, self.prompt_len)
        s = len(prompt)
        if s + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({s}) + max_new ({req.max_new}) "
                f"exceeds max_len={self.max_len}")
        n_total = -(-(s + req.max_new) // self.page_size)
        entry = self.prefix.match(prompt) if self.prefix else None
        if entry is not None:
            # suffix >= 1 token (match is strictly shorter), so
            # n_total > len(entry.pages) and at least one fresh page fits
            # the first decode slot. The shared reference is taken BEFORE
            # allocating: _alloc_pages evicts refcount-1 prefix chains
            # under pool pressure, and the matched entry is refcount-1
            # until this request references it — sharing first (refcount
            # 2) keeps it off the eviction list while the admit needs it.
            self.allocator.share(entry.pages)
            new_pages = self._alloc_pages(n_total - len(entry.pages))
            if new_pages is None:
                self.allocator.free(entry.pages)   # roll the share back
                return False
            self._insert_shared(row, req, prompt, entry, new_pages)
            return True
        pages = self._alloc_pages(n_total)       # reserve the whole chain
        if pages is None:
            return False
        self._insert(row, req, prompt, pages)
        return True

    def _alloc_pages(self, n: int) -> Optional[list[int]]:
        """Allocate, evicting LRU unreferenced prefixes under pressure."""
        pages = self.allocator.alloc(n)
        while pages is None and self.prefix.evict_lru(self.allocator):
            pages = self.allocator.alloc(n)
        return pages

    def _prefill_prompt(self, prompt: np.ndarray):
        """Prefill at the canonical padded width (attention families) or
        exact length (recurrent families). Logits are for the last *real*
        position either way."""
        if not self._pad_prompts:
            toks = jnp.asarray(prompt, jnp.int32)[None, :]
            return self._prefill_one(self.params, toks)
        padded = np.zeros(self.prompt_len, np.int32)
        padded[:len(prompt)] = prompt
        return self._prefill_one(self.params, jnp.asarray(padded)[None, :],
                                 jnp.int32(len(prompt)))

    def _insert(self, row: int, req: Request, prompt: np.ndarray,
                pages: list[int]) -> None:
        logits, cache1, _ = self._prefill_prompt(prompt)
        pool1, state1 = _split_tree(cache1, self._pool_layout,
                                    self._state_layout)
        (self.pool, self.state, self.page_table, self.lengths,
         self.tokens, nxt) = self._insert_fused(
            self.pool, self.state, self.page_table, self.lengths,
            self.tokens, pool1, state1, logits, jnp.int32(row),
            jnp.asarray(pages, jnp.int32), jnp.int32(len(prompt)))
        req.out.append(int(nxt))                 # one device sync per admit
        self.active[row] = req
        self._chains[row] = list(pages)
        self._len_host[row] = len(prompt)
        self.prefill_tokens += len(prompt)
        self._state_version += 1
        self._page_versions[pages] = self._state_version

    def _insert_shared(self, row: int, req: Request, prompt: np.ndarray,
                       entry: prefix_lib.PrefixEntry,
                       new_pages: list[int]) -> None:
        """COW admit: continuation-prefill the suffix, scatter into fresh
        pages, map [shared chain ; fresh pages] into the row's table."""
        p0 = entry.length
        if self._cont_prefill is None:
            self._cont_prefill = jax.jit(prefix_lib.make_continue_prefill(
                self.cfg, self.page_size))
        shared_ids = jnp.asarray(entry.pages, jnp.int32)
        # suffixes right-pad to ONE canonical width — the longest suffix
        # any registered prefix can leave (prompt_len - page_size) — so
        # every shared admit runs one compiled continuation shape per
        # prefix, mirroring the padded full prefill: XLA rounding must
        # not depend on this request's suffix length.
        suffix = prompt[p0:]
        padded = np.zeros(self.prompt_len - self.page_size, np.int32)
        padded[:len(suffix)] = suffix
        logits, kv1 = self._cont_prefill(self.params, self.pool,
                                         shared_ids,
                                         jnp.asarray(padded)[None, :],
                                         jnp.int32(len(suffix)))
        (self.pool, self.page_table, self.lengths, self.tokens,
         nxt) = self._insert_suffix(
            self.pool, self.page_table, self.lengths, self.tokens,
            {"kv": kv1}, logits, jnp.int32(row), shared_ids,
            jnp.asarray(new_pages, jnp.int32), jnp.int32(len(prompt)))
        req.out.append(int(nxt))                 # one device sync per admit
        self.active[row] = req
        self._chains[row] = list(entry.pages) + list(new_pages)
        self._len_host[row] = len(prompt)
        self.prefill_tokens += len(prompt) - p0
        self.shared_tokens += p0
        self._state_version += 1
        self._page_versions[new_pages] = self._state_version

    def register_prefix(self, tokens: Any) -> str:
        """Prefill a shared prompt prefix once and pin its page chain.

        The prefix is truncated to a whole number of pages (sharing is
        page-granular) that leaves room for at least one divergent prompt
        token inside the prompt window. Registering the same tokens twice
        is a no-op returning the same key. The chain is owned by the
        prefix cache at refcount 1; each matching admit adds a reference.
        """
        if self.cfg.family not in prefix_lib.SHAREABLE_FAMILIES:
            raise ValueError(
                f"prefix sharing needs every cache leaf in the page pool; "
                f"family {self.cfg.family!r} keeps per-row recurrent state "
                f"that cannot be shared read-only")
        toks = np.asarray(tokens, np.int32).reshape(-1)
        p0 = (min(len(toks), self.prompt_len - 1)
              // self.page_size * self.page_size)
        if p0 < self.page_size:
            raise ValueError(
                f"prefix of {len(toks)} tokens is shorter than one page "
                f"({self.page_size}) after truncation to the prompt "
                f"window ({self.prompt_len})")
        toks = np.ascontiguousarray(toks[:p0])
        key = prefix_lib.prefix_key(toks)
        if self.prefix.get(key) is not None:
            return key
        pages = self._alloc_pages(p0 // self.page_size)
        if pages is None:
            raise RuntimeError(
                f"cannot register prefix: {p0 // self.page_size} pages "
                f"needed, {self.allocator.free_pages} free")
        logits, cache1, _ = self._prefill_prompt(toks)
        del logits                               # chain ends mid-prompt
        pool1, _ = _split_tree(cache1, self._pool_layout,
                               self._state_layout)
        self.pool = self._register_insert(self.pool, pool1,
                                          jnp.asarray(pages, jnp.int32))
        self.prefill_tokens += p0
        self._state_version += 1
        self._page_versions[pages] = self._state_version
        self.prefix.add(prefix_lib.PrefixEntry(key=key, tokens=toks,
                                               pages=list(pages)))
        return key

    def unregister_prefix(self, key: str) -> bool:
        """Drop a registered prefix (by the key ``register_prefix``
        returned): the cache's own reference is released and new admits
        stop matching it. In-flight requests that already map the chain
        keep their refcounts — the pages return to the pool when the last
        of them completes. Unknown keys return False.
        """
        return self.prefix.drop(key, self.allocator)

    def free_resource(self, row: int) -> None:
        """Return the chain to the pool and point the row at scratch."""
        self.allocator.free(self._chains[row])
        self._chains[row] = []
        self.active[row] = None
        self.page_table, self.lengths = self._clear_row(
            self.page_table, self.lengths, jnp.int32(row))
        self._len_host[row] = 0

    def step(self) -> None:
        nxt, self.tokens, self.pool, self.state, self.lengths = self._decode(
            self.params, self.pool, self.state, self.page_table,
            self.tokens, self.lengths)
        self._state_version += 1
        nxt_host = np.asarray(nxt)               # one device->host transfer
        for r, req in enumerate(self.active):
            if req is None:
                continue
            req.out.append(int(nxt_host[r]))
            pos = self._len_host[r]              # slot this decode wrote
            self._page_versions[self._chains[r][pos // self.page_size]] = \
                self._state_version
            self._len_host[r] += 1
            if len(req.out) >= req.max_new:
                req.done = True
                self.free_resource(r)

    def run(self, requests: list[Request], max_steps: int = 512) -> None:
        pending = list(requests)
        for _ in range(max_steps):
            while pending and self.admit(pending[0]):
                pending.pop(0)
            if not pending and all(a is None for a in self.active):
                return
            if any(a is not None for a in self.active):
                self.step()

    # -- introspection / in-situ --------------------------------------------

    @property
    def state_version(self) -> int:
        return self._state_version

    def page_stats(self) -> dict[str, float]:
        used = (self.num_pages - 1) - self.allocator.free_pages
        refs = self.allocator.refcounts()
        return {
            "num_pages": self.num_pages,
            "free_pages": self.allocator.free_pages,
            "used_pages": used,
            "page_utilization": used / max(1, self.num_pages - 1),
            "active_requests": sum(a is not None for a in self.active),
            "occupancy": (sum(a is not None for a in self.active)
                          / self.max_reqs),
            "shared_pages": sum(1 for c in refs.values() if c > 1),
        }

    def prefix_stats(self) -> dict[str, Any]:
        """Prefix-cache effectiveness: hit rate, sharing, tokens saved.

        ``pages_saved`` counts extra references — pages a request mapped
        instead of allocating+prefilling its own copy. ``shared_tokens``
        is the prompt-token count served from shared pages (the prefill
        work sharing avoided); ``prefill_tokens`` is what actually ran.
        """
        refs = self.allocator.refcounts()
        st = self.prefix.stats()
        st.update({
            "shared_pages": sum(1 for c in refs.values() if c > 1),
            "pages_saved": sum(c - 1 for c in refs.values()),
            "prefill_tokens": self.prefill_tokens,
            "shared_tokens": self.shared_tokens,
        })
        return st

    def snapshot_payload(self) -> dict[str, Any]:
        """serve_snapshot payload: pool + state + tables + host metadata.

        ``chunk_hints`` sizes each pool leaf's delta chunks to one
        (layer, page) slab and ``page_versions`` records which pages moved,
        so unchanged pages frame as zero-payload COPY ops in the store.

        The ``meta`` leaf is the host-side engine state as JSON bytes —
        allocator free list + refcounts, request chains, in-flight
        requests, registered prefixes — everything :meth:`from_snapshot`
        needs to hydrate a cold replica that serves its next token without
        re-prefilling (its byte length varies, which the delta codec
        handles by framing it self-contained whenever it changes size).
        """
        meta = {
            "engine": {"num_pages": self.num_pages,
                       "page_size": self.page_size,
                       "max_reqs": self.max_reqs,
                       "prompt_len": self.prompt_len,
                       "max_len": self.max_len},
            "allocator": self.allocator.state_dict(),
            "chains": [list(c) for c in self._chains],
            "len_host": self._len_host.tolist(),
            "active": [None if a is None else
                       {"rid": a.rid,
                        "prompt": np.asarray(a.prompt).tolist(),
                        "max_new": a.max_new, "out": list(a.out)}
                       for a in self.active],
            "prefix": self.prefix.state_dict(),
            "counters": {"prefill_tokens": self.prefill_tokens,
                         "shared_tokens": self.shared_tokens},
            "version": self._state_version,
            "page_versions": self._page_versions.tolist(),
        }
        meta_leaf = np.frombuffer(json.dumps(meta).encode(), np.uint8)
        cache = {"pool": self.pool, "state": self.state,
                 "page_table": self.page_table, "lengths": self.lengths,
                 "tokens": self.tokens, "meta": meta_leaf}
        return {"cache": cache, "version": self._state_version,
                "page_versions": self._page_versions.copy(),
                "chunk_hints": dict(self._chunk_hints)}

    # -- replica hydration ----------------------------------------------------

    @classmethod
    def from_snapshot(cls, cfg: ModelConfig, params,
                      leaves: Mapping[str, np.ndarray]
                      ) -> "PagedServingEngine":
        """Rebuild an engine from a restored ``kv_pages`` snapshot.

        ``leaves`` is ``SnapshotStore.restore``'s flattened-key mapping.
        The ``meta`` leaf fixes the engine geometry and the host state;
        the array leaves refill the device slabs bit-exactly. The result
        decodes in lockstep with the producer at snapshot time: same page
        pool, same tables, same in-flight requests, same registered
        prefixes — first token without any prefill.
        """
        try:
            meta_leaf = leaves["['meta']"]
        except KeyError:
            raise KeyError(
                "snapshot has no 'meta' leaf — it was published by an "
                "engine without hydration metadata (pre-prefix-sharing "
                "chain); re-publish from a current engine") from None
        meta = json.loads(np.asarray(meta_leaf, np.uint8).tobytes())
        eng = cls(cfg, params, **{k: int(v)
                                  for k, v in meta["engine"].items()})
        eng._apply_snapshot(leaves, meta)
        return eng

    def load_snapshot(self, leaves: Mapping[str, np.ndarray]) -> None:
        """Re-hydrate *this* engine in place from a restored snapshot.

        Same effect as :meth:`from_snapshot` but reuses the engine's
        compiled decode/prefill functions (jit caches are per-instance) —
        the warm path for repeated catch-up from a newer chain point, and
        what TTFT benchmarks time so they measure restore work rather
        than retracing.
        """
        meta = json.loads(np.asarray(leaves["['meta']"], np.uint8).tobytes())
        geo = {k: int(v) for k, v in meta["engine"].items()}
        mine = {"num_pages": self.num_pages, "page_size": self.page_size,
                "max_reqs": self.max_reqs, "prompt_len": self.prompt_len,
                "max_len": self.max_len}
        if geo != mine:
            raise ValueError(f"snapshot geometry {geo} does not match "
                             f"this engine {mine}; use from_snapshot()")
        self._apply_snapshot(leaves, meta)

    def _apply_snapshot(self, leaves: Mapping[str, np.ndarray],
                        meta: Mapping[str, Any]) -> None:
        template = {"pool": self.pool, "state": self.state,
                    "page_table": self.page_table, "lengths": self.lengths,
                    "tokens": self.tokens}
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        out = []
        for path, leaf in flat:
            key = jax.tree_util.keystr(path)
            if key not in leaves:
                raise KeyError(f"snapshot is missing leaf {key} "
                               f"(engine geometry drifted since publish)")
            out.append(jnp.asarray(leaves[key], leaf.dtype))
        restored = jax.tree_util.tree_unflatten(treedef, out)
        self.pool = restored["pool"]
        self.state = restored["state"]
        self.page_table = restored["page_table"]
        self.lengths = restored["lengths"]
        self.tokens = restored["tokens"]
        self.allocator.load_state(meta["allocator"])
        self._chains = [[int(p) for p in c] for c in meta["chains"]]
        self._len_host = np.asarray(meta["len_host"], np.int64)
        self.active = [
            None if a is None else Request(
                rid=int(a["rid"]),
                prompt=np.asarray(a["prompt"], np.int32),
                max_new=int(a["max_new"]),
                out=[int(t) for t in a["out"]])
            for a in meta["active"]]
        self.prefix.load_state(meta["prefix"])
        self.prefill_tokens = int(meta["counters"]["prefill_tokens"])
        self.shared_tokens = int(meta["counters"]["shared_tokens"])
        self._state_version = int(meta["version"])
        self._page_versions = np.asarray(meta["page_versions"], np.int64)

    def insitu_providers(self) -> dict[str, Callable[[], Any]]:
        return {"serving_state": lambda: {"pool": self.pool,
                                          "state": self.state},
                "lengths": lambda: self.lengths,
                "page_stats": lambda: self.page_stats(),
                "kv_snapshot": lambda: self.snapshot_payload()}
