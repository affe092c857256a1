"""Paged KV cache + continuous batching: parity, allocator, snapshots.

The load-bearing claim is *bit-identity*: the paged engine gathers its
pages into token order and masks positions past the length with NEG_INF,
whose softmax weight underflows to exactly 0.0 — so paged logits are
bitwise equal to the dense engine's, and greedy decode produces the same
tokens. The parity suite pins that across every attention family; the
allocator and snapshot tests pin the lifecycle invariants the engine's
safety argument rests on (whole-chain reservation, no double-assign,
page-aligned delta COPY framing).

MoE caveat: expert capacity couples batch rows, so parity over MoE archs
requires the same batch width and a free/admit schedule that keeps active
rows aligned — the suite uses equal ``max_new`` so both engines retire
requests in the same order.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import delta
from repro.models import attention as attn_lib
from repro.models import params as P
from repro.models import transformer
from repro.serving import pages as PG
from repro.serving.engine import Request, ServingEngine
from repro.serving.snapshot import SnapshotStore

PARITY_ARCHS = ["smollm-135m", "deepseek-v3-671b", "moonlight-16b-a3b",
                "hymba-1.5b", "xlstm-1.3b"]


def _mk(arch):
    cfg = base.get(arch, smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    return cfg, prm


# ---------------------------------------------------------------------------
# decode parity: paged engine bit-identical to dense slots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_engine_matches_dense(arch):
    cfg, prm = _mk(arch)
    rng = np.random.default_rng(0)
    mk_reqs = lambda: [Request(i, rng0, max_new=4) for i, rng0 in
                       enumerate([rng.integers(0, cfg.vocab_size, 8)
                                  for _ in range(3)])]
    a, b = mk_reqs(), mk_reqs()
    for (ra, rb) in zip(a, b):
        rb.prompt = ra.prompt                    # identical streams

    dense = ServingEngine(cfg, prm, slots=2, prompt_len=8, max_len=64)
    dense.run(a, max_steps=64)
    paged = PG.PagedServingEngine(cfg, prm, num_pages=9, page_size=16,
                                  max_reqs=2, prompt_len=8, max_len=64)
    paged.run(b, max_steps=64)
    for ra, rb in zip(a, b):
        assert ra.done and rb.done
        assert ra.out == rb.out, f"request {ra.rid} diverged"


def test_paged_gather_bitwise_equals_dense_attention(rng):
    """Gathered pages + length mask == contiguous decode attention, bit for
    bit — the kernel-independent core of the parity argument."""
    b, pps, ps, n_kv, hq, d = 2, 3, 8, 2, 4, 8
    s = pps * ps
    k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.float32)
    lengths = jnp.asarray([13, s], jnp.int32)

    # scatter rows into a shared pool at arbitrary (non-contiguous) pages
    table = jnp.asarray([[5, 1, 4], [2, 7, 3]], jnp.int32)
    kp = jnp.zeros((9, ps, n_kv, d), jnp.float32)
    vp = jnp.zeros((9, ps, n_kv, d), jnp.float32)
    for row in range(b):
        for j in range(pps):
            pg = int(table[row, j])
            kp = kp.at[pg].set(k[row, j * ps:(j + 1) * ps])
            vp = vp.at[pg].set(v[row, j * ps:(j + 1) * ps])
    # each row only sees its own pages, so per-row gather from the shared
    # pool must reproduce that row's contiguous sequence
    g = attn_lib.gather_pages(kp, table)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(k))

    out = attn_lib.paged_decode_attention(q, kp, vp, table, lengths,
                                          use_kernel=False)
    ref = attn_lib.decode_attention(q, k, v, lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_scatter_token_lands_in_length_slot(rng):
    b, pps, ps, n_kv, d = 2, 3, 8, 2, 4
    pages = jnp.zeros((9, ps, n_kv, d), jnp.float32)
    table = jnp.asarray([[5, 1, 4], [2, 7, 3]], jnp.int32)
    new = jnp.asarray(rng.standard_normal((b, n_kv, d)), jnp.float32)
    lengths = jnp.asarray([5, 17], jnp.int32)
    out = attn_lib.scatter_token(pages, new, table, lengths, ps)
    np.testing.assert_array_equal(np.asarray(out[5, 5]),
                                  np.asarray(new[0]))   # row 0: chain idx 0
    np.testing.assert_array_equal(np.asarray(out[3, 1]),
                                  np.asarray(new[1]))   # row 1: 17 -> idx 2
    # exactly two slots written
    assert int((out != 0).sum()) == 2 * n_kv * d


def test_paged_attention_kernel_matches_gather(rng):
    """The Pallas kernel (interpret mode off-TPU) vs the gather fallback."""
    from repro.kernels import paged_attention as PK

    b, pps, ps, n_kv, hq, d = 2, 3, 8, 2, 4, 8
    kp = jnp.asarray(rng.standard_normal((9, ps, n_kv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((9, ps, n_kv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, d)), jnp.float32)
    table = jnp.asarray([[5, 1, 4], [2, 7, 3]], jnp.int32)
    lengths = jnp.asarray([13, 24], jnp.int32)
    out = PK.paged_decode_attention(q, kp, vp, table, lengths,
                                    interpret=True)
    ref = attn_lib.paged_decode_attention(q, kp, vp, table, lengths,
                                          use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# allocator properties
# ---------------------------------------------------------------------------

def test_allocator_never_double_assigns():
    a = PG.PageAllocator(8)                      # pages 1..7 usable
    x = a.alloc(3)
    y = a.alloc(4)
    assert x is not None and y is not None
    assert not set(x) & set(y)
    assert 0 not in x + y                        # scratch page never handed out
    assert a.free_pages == 0


def test_allocator_exhaustion_rejects_without_mutation():
    a = PG.PageAllocator(8)
    a.alloc(5)
    before = a.free_pages
    assert a.alloc(3) is None                    # 2 free < 3 wanted
    assert a.free_pages == before                # rejected alloc is a no-op
    assert a.alloc(2) is not None


def test_allocator_free_restores_and_guards():
    a = PG.PageAllocator(8)
    x = a.alloc(3)
    y = a.alloc(4)
    a.free(x)
    assert a.free_pages == 3
    z = a.alloc(2)
    assert set(z) <= set(x)                      # reuses released pages
    a.free(y)
    with pytest.raises(ValueError):
        a.free(y)                                # double free
    with pytest.raises(ValueError):
        a.free([99])                             # foreign page


def test_engine_reclaims_all_pages():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(1)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 8),
                    max_new=int(m))
            for i, m in enumerate([4, 24, 8, 16, 4, 8])]
    eng = PG.PagedServingEngine(cfg, prm, num_pages=9, page_size=8,
                                max_reqs=3, prompt_len=8, max_len=32)
    eng.run(reqs, max_steps=256)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == r.max_new for r in reqs)
    assert eng.allocator.free_pages == eng.num_pages - 1    # no leak
    assert all(not c for c in eng._chains)
    assert eng.page_stats()["used_pages"] == 0


def test_admit_rejects_on_page_exhaustion_then_recovers():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(2)
    eng = PG.PagedServingEngine(cfg, prm, num_pages=3, page_size=8,
                                max_reqs=4, prompt_len=8, max_len=16)
    ra = Request(0, rng.integers(0, cfg.vocab_size, 8), max_new=8)
    rb = Request(1, rng.integers(0, cfg.vocab_size, 8), max_new=8)
    assert eng.admit(ra)                         # takes both usable pages
    assert not eng.admit(rb)                     # rows free, pages aren't
    while any(a is not None for a in eng.active):
        eng.step()
    assert ra.done
    assert eng.admit(rb)                         # reclaimed pages readmit


def test_admit_rejects_requests_that_cannot_fit():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(3)
    eng = PG.PagedServingEngine(cfg, prm, num_pages=5, page_size=8,
                                max_reqs=2, prompt_len=8, max_len=16)
    with pytest.raises(ValueError, match="max_new"):
        eng.admit(Request(0, rng.integers(0, cfg.vocab_size, 8),
                          max_new=16))           # 8 + 16 > max_len


def test_page_size_must_divide_max_len():
    cfg, prm = _mk("smollm-135m")
    with pytest.raises(ValueError, match="multiple"):
        PG.PagedServingEngine(cfg, prm, num_pages=5, page_size=12,
                              max_reqs=2, prompt_len=8, max_len=64)


# ---------------------------------------------------------------------------
# prompt truncation warns instead of silently dropping tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_long_prompt_warns_and_truncates(kind):
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(0, cfg.vocab_size, 12)
    if kind == "dense":
        eng = ServingEngine(cfg, prm, slots=1, prompt_len=8, max_len=32)
    else:
        eng = PG.PagedServingEngine(cfg, prm, num_pages=5, page_size=8,
                                    max_reqs=1, prompt_len=8, max_len=32)
    r = Request(0, long_prompt, max_new=4)
    with pytest.warns(RuntimeWarning, match="request 0.*exceeds"):
        eng.run([r], max_steps=16)
    assert r.done and len(r.out) == 4
    # the tail of the prompt is what survives: same completion as submitting
    # the truncated prompt explicitly (no warning that time)
    r2 = Request(1, long_prompt[-8:], max_new=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng.run([r2], max_steps=16)
    assert r2.out == r.out


# ---------------------------------------------------------------------------
# page-granular snapshots: dirty tracking + delta COPY alignment
# ---------------------------------------------------------------------------

def test_page_versions_track_exactly_the_touched_pages():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(5)
    eng = PG.PagedServingEngine(cfg, prm, num_pages=9, page_size=8,
                                max_reqs=2, prompt_len=8, max_len=32)
    assert eng.admit(Request(0, rng.integers(0, cfg.vocab_size, 8),
                             max_new=8))         # 16 tokens -> 2 pages
    chain = list(eng._chains[0])
    pv1 = eng.snapshot_payload()["page_versions"]
    assert (pv1[chain] > 0).all()                # admit stamped the chain
    untouched = np.setdiff1d(np.arange(eng.num_pages), chain)
    assert (pv1[untouched] == 0).all()

    eng.step()                                   # writes slot 8 -> chain[1]
    pv2 = eng.snapshot_payload()["page_versions"]
    assert pv2[chain[1]] > pv1[chain[1]]
    stable = np.setdiff1d(np.arange(eng.num_pages), [chain[1]])
    np.testing.assert_array_equal(pv2[stable], pv1[stable])


def test_delta_chunks_align_to_pages():
    """With the engine's chunk hints, one decode step dirties exactly one
    page, and every other (layer, page) slab frames as a zero-payload COPY."""
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(6)
    eng = PG.PagedServingEngine(cfg, prm, num_pages=9, page_size=8,
                                max_reqs=2, prompt_len=8, max_len=32)
    eng.admit(Request(0, rng.integers(0, cfg.vocab_size, 8), max_new=8))
    p1 = eng.snapshot_payload()
    eng.step()
    p2 = eng.snapshot_payload()

    flat1 = jax.tree_util.tree_flatten_with_path(
        {"pool": p1["cache"]["pool"]})[0]
    flat2 = jax.tree_util.tree_flatten_with_path(
        {"pool": p2["cache"]["pool"]})[0]
    assert flat1, "paged pool must not be empty for an attention arch"
    for (path, base_leaf), (_, cur_leaf) in zip(flat1, flat2):
        key = jax.tree_util.keystr(path)
        hint = p2["chunk_hints"][key]
        layers, num_pages = base_leaf.shape[:2]
        assert hint == int(np.prod(base_leaf.shape[2:])) * \
            base_leaf.dtype.itemsize
        _, st = delta.encode(np.asarray(cur_leaf), np.asarray(base_leaf),
                             chunk_bytes=hint)
        # the step dirties one chain page, plus the scratch page 0 where
        # the inactive row's masked write lands; every other (layer, page)
        # slab must frame as a zero-payload COPY
        assert st.n_copy >= layers * (num_pages - 2)
        assert st.n_copy < layers * num_pages


def test_snapshot_store_roundtrip_with_chunk_hints():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(7)
    eng = PG.PagedServingEngine(cfg, prm, num_pages=9, page_size=8,
                                max_reqs=2, prompt_len=8, max_len=32)
    eng.admit(Request(0, rng.integers(0, cfg.vocab_size, 8), max_new=8))
    store = SnapshotStore(base_every=4)
    p1 = eng.snapshot_payload()
    r1 = store.publish("kv", 0, p1["cache"], version=p1["version"],
                       chunk_hints=p1["chunk_hints"])
    assert r1.kind == "base"
    eng.step()
    p2 = eng.snapshot_payload()
    r2 = store.publish("kv", 1, p2["cache"], version=p2["version"],
                       chunk_hints=p2["chunk_hints"])
    assert r2.kind == "delta"
    step, tree = store.restore("kv", template=p2["cache"])
    assert step == 1
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(p2["cache"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
