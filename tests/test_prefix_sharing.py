"""Shared-prefix COW page cache + snapshot-hydrated replicas.

Two load-bearing claims, both variants of bit-identity:

* **sharing is invisible** — requests that map a registered prefix chain
  read-only and prefill only their suffix decode token-for-token the same
  stream as requests that prefilled the whole prompt themselves. The only
  observable difference is the counter: far fewer prompt tokens prefilled.
* **hydration is exact** — a replica rebuilt from the snapshot chain
  (pool, tables, allocator free list + refcounts, registered prefixes,
  in-flight requests) decodes in lockstep with the producer from the
  first step, with zero prefill of its own.

Both rest on the refcount invariants of the ``PageAllocator``: a
referenced page is never reclaimed, over-free raises instead of
corrupting the free list, and eviction only ever takes chains no request
still maps. Those are property-tested (hypothesis when available, and a
seeded deterministic interleaving that always runs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.configs import base
from repro.models import params as P
from repro.models import transformer
from repro.serving import pages as PG
from repro.serving import prefix as PX
from repro.serving.engine import Request

SHAREABLE_ARCHS = ["smollm-135m", "deepseek-v3-671b", "moonlight-16b-a3b"]
STATEFUL_ARCHS = ["hymba-1.5b", "xlstm-1.3b"]


def _mk(arch):
    cfg = base.get(arch, smoke=True)
    prm = P.materialize(jax.random.PRNGKey(0), transformer.param_spec(cfg))
    return cfg, prm


def _mk_engine(cfg, prm, **kw):
    kw.setdefault("num_pages", 17)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_reqs", 4)
    kw.setdefault("prompt_len", 24)
    kw.setdefault("max_len", 64)
    return PG.PagedServingEngine(cfg, prm, **kw)


def _mk_requests(cfg, rng, n, prefix, max_new=4):
    return [Request(i, np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, size=4)]), max_new=max_new)
        for i in range(n)]


# ---------------------------------------------------------------------------
# sharing parity: COW-mapped prefixes decode the exact same tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", SHAREABLE_ARCHS)
def test_shared_prefix_token_parity(arch):
    cfg, prm = _mk(arch)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    a = _mk_requests(cfg, rng, 4, prefix)
    b = [Request(r.rid, r.prompt.copy(), max_new=r.max_new) for r in a]

    plain = _mk_engine(cfg, prm)
    plain.run(a, max_steps=64)
    shared = _mk_engine(cfg, prm)
    shared.register_prefix(prefix)
    shared.run(b, max_steps=64)

    for ra, rb in zip(a, b):
        assert ra.done and rb.done
        assert ra.out == rb.out, f"request {ra.rid} diverged under sharing"

    ps, pp = plain.prefix_stats(), shared.prefix_stats()
    assert ps["prefill_tokens"] == 4 * 20         # every prompt in full
    assert pp["prefill_tokens"] == 16 + 4 * 4     # prefix once + suffixes
    assert pp["shared_tokens"] == 4 * 16
    assert pp["hits"] == 4 and pp["misses"] == 0 and pp["hit_rate"] == 1.0
    # everything retired, so only the cache's own reference remains
    assert shared.prefix_stats()["pages_saved"] == 0
    assert shared.allocator.refcounts() == {1: 1}  # the pinned prefix page


def test_shared_pages_counted_while_requests_are_live():
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(1)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    eng = _mk_engine(cfg, prm)
    eng.register_prefix(prefix)
    reqs = _mk_requests(cfg, rng, 3, prefix, max_new=8)
    for r in reqs:
        assert eng.admit(r)
    st_ = eng.prefix_stats()
    assert st_["shared_pages"] == 1               # the one prefix page
    assert st_["pages_saved"] == 3                # three COW references
    assert eng.allocator.refcount(eng.prefix.entries()[0].pages[0]) == 4
    eng.run(reqs, max_steps=64)
    assert all(r.done for r in reqs)
    assert eng.prefix_stats()["pages_saved"] == 0


def test_prompt_equal_to_prefix_is_not_shared():
    """The continuation prefill needs >= 1 divergent token, so a prompt
    exactly equal to a registered prefix prefills normally (miss)."""
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    eng = _mk_engine(cfg, prm)
    eng.register_prefix(prefix)
    req = Request(0, prefix.copy(), max_new=2)
    eng.run([req], max_steps=32)
    assert req.done
    st_ = eng.prefix_stats()
    assert st_["hits"] == 0 and st_["misses"] == 1


@pytest.mark.parametrize("arch", STATEFUL_ARCHS)
def test_register_prefix_rejects_stateful_families(arch):
    cfg, prm = _mk(arch)
    eng = _mk_engine(cfg, prm)
    with pytest.raises(ValueError, match="per-row recurrent state"):
        eng.register_prefix(np.arange(16))


def test_register_prefix_too_short_raises():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm)
    with pytest.raises(ValueError, match="shorter than one page"):
        eng.register_prefix(np.arange(7))


def test_register_prefix_idempotent():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm)
    toks = np.arange(16)
    k1 = eng.register_prefix(toks)
    free_after = eng.allocator.free_pages
    k2 = eng.register_prefix(toks)
    assert k1 == k2
    assert eng.allocator.free_pages == free_after
    assert len(eng.prefix) == 1


def test_prefix_match_longest_strictly_shorter():
    cache = PX.PrefixCache()
    short = np.arange(16, dtype=np.int32)
    long = np.arange(32, dtype=np.int32)
    cache.add(PX.PrefixEntry(key="s", tokens=short, pages=[1]))
    cache.add(PX.PrefixEntry(key="l", tokens=long, pages=[2, 3]))
    hit = cache.match(np.arange(40))
    assert hit is not None and hit.key == "l"     # longest wins
    hit = cache.match(np.arange(32))              # equal length -> shorter
    assert hit is not None and hit.key == "s"
    assert cache.match(np.arange(3, 40)) is None  # content mismatch


# ---------------------------------------------------------------------------
# allocator refcount invariants
# ---------------------------------------------------------------------------

def _check_allocator_invariants(alloc):
    live = alloc.refcounts()
    assert all(c >= 1 for c in live.values())
    assert not (set(alloc._free) & set(live))       # free xor referenced
    assert len(set(alloc._free)) == len(alloc._free)
    assert len(alloc._free) + len(live) == alloc.num_pages - 1
    assert 0 not in live and 0 not in alloc._free   # scratch never managed


def _drive_allocator(num_pages, ops):
    """Replay (op, arg) interleavings; invalid frees/shares must raise and
    leave state untouched. Returns the allocator for final checks."""
    alloc = PG.PageAllocator(num_pages)
    chains = []                                      # live chains we own
    for op, arg in ops:
        if op == "alloc":
            pages = alloc.alloc(1 + arg % 3)
            if pages is not None:
                chains.append(pages)
        elif op == "share" and chains:
            pages = chains[arg % len(chains)]
            alloc.share(pages)
            chains.append(list(pages))
        elif op == "free" and chains:
            alloc.free(chains.pop(arg % len(chains)))
        elif op == "bad_free":
            before = alloc.state_dict()
            freed = {p for c in chains for p in c}
            victim = next((p for p in range(1, num_pages)
                           if p not in freed and alloc.refcount(p) == 0),
                          None)
            if victim is not None:
                with pytest.raises(ValueError):
                    alloc.free([victim])            # double/foreign free
                assert alloc.state_dict() == before
        _check_allocator_invariants(alloc)
    return alloc, chains


def test_allocator_interleavings_deterministic():
    rng = np.random.default_rng(0)
    names = ["alloc", "share", "free", "bad_free"]
    for trial in range(25):
        ops = [(names[rng.integers(0, 4)], int(rng.integers(0, 100)))
               for _ in range(40)]
        alloc, chains = _drive_allocator(9, ops)
        for c in list(chains):                      # full drain reclaims all
            alloc.free(c)
        assert alloc.free_pages == 8
        assert alloc.refcounts() == {}


@settings(max_examples=50, deadline=None)
@given(ops=st.lists(st.tuples(
    st.sampled_from(["alloc", "share", "free", "bad_free"]),
    st.integers(min_value=0, max_value=99)), max_size=60))
def test_allocator_interleavings_property(ops):
    alloc, chains = _drive_allocator(9, ops)
    for c in list(chains):
        alloc.free(c)
    assert alloc.free_pages == 8 and alloc.refcounts() == {}


def test_referenced_page_survives_owner_free():
    alloc = PG.PageAllocator(5)
    chain = alloc.alloc(2)
    alloc.share(chain)                              # a reader maps it
    alloc.free(chain)                               # the owner retires
    assert all(alloc.refcount(p) == 1 for p in chain)
    assert not (set(alloc._free) & set(chain))      # never reclaimed
    alloc.free(chain)                               # reader retires
    assert alloc.free_pages == 4


def test_eviction_only_takes_unreferenced_chains():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm, num_pages=6, prompt_len=40, max_len=48)
    rng = np.random.default_rng(3)
    pinned = rng.integers(0, cfg.vocab_size, size=16)
    idle = rng.integers(0, cfg.vocab_size, size=32)
    k_pin = eng.register_prefix(pinned)
    k_idle = eng.register_prefix(idle)
    req = Request(0, np.concatenate(
        [pinned, rng.integers(0, cfg.vocab_size, size=4)]), max_new=8)
    assert eng.admit(req)                           # holds a ref on pinned
    # pool pressure: the next admit must evict, and must pick the idle
    # chain (LRU among refcount-1 chains), never the one req still maps
    assert eng.prefix.evict_lru(eng.allocator)
    assert eng.prefix.get(k_idle) is None
    assert eng.prefix.get(k_pin) is not None
    assert not eng.prefix.evict_lru(eng.allocator)  # pinned chain is shared
    eng.run([req], max_steps=64)
    assert req.done


def test_admit_evicts_lru_prefix_under_pressure():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm, num_pages=4, prompt_len=24, max_len=48)
    rng = np.random.default_rng(4)
    eng.register_prefix(rng.integers(0, cfg.vocab_size, size=16))
    assert eng.allocator.free_pages == 2
    # a 3-page admit only fits if the idle prefix chain is evicted
    req = Request(0, rng.integers(0, cfg.vocab_size, size=20), max_new=16)
    assert eng.admit(req)
    assert len(eng.prefix) == 0
    assert eng.prefix.stats()["evictions"] == 1
    eng.run([req], max_steps=64)
    assert req.done


def test_matching_admit_under_pressure_never_evicts_its_own_prefix():
    """A shared admit must not LRU-evict the very prefix it just matched:
    the shared reference is taken before suffix allocation, so under
    pressure the matched chain is refcount-2 (never an eviction
    candidate) and a pool that can't fit the suffix rejects cleanly —
    allocator and prefix cache bit-identical to before the attempt."""
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm, num_pages=4, prompt_len=24, max_len=48)
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    key = eng.register_prefix(prefix)
    blocker = Request(0, rng.integers(0, cfg.vocab_size, size=16),
                      max_new=16)
    assert eng.admit(blocker)                   # 2 pages -> pool exhausted
    assert eng.allocator.free_pages == 0
    req = Request(1, np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, size=4)]), max_new=8)
    before_alloc = eng.allocator.state_dict()
    before_pages = list(eng.prefix.get(key).pages)
    assert not eng.admit(req)                   # clean rejection, no evict
    assert eng.allocator.state_dict() == before_alloc
    assert eng.prefix.get(key) is not None
    assert eng.prefix.get(key).pages == before_pages
    assert eng.prefix.stats()["evictions"] == 0
    for _ in range(32):                          # drain the blocker
        if blocker.done:
            break
        eng.step()
    assert blocker.done
    assert eng.admit(req)                        # now it shares normally
    assert eng.allocator.refcount(before_pages[0]) == 2
    for _ in range(16):
        if req.done:
            break
        eng.step()
    assert req.done


def test_unregister_prefix_releases_cache_reference():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm)
    rng = np.random.default_rng(8)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    key = eng.register_prefix(prefix)
    req = _mk_requests(cfg, rng, 1, prefix, max_new=4)[0]
    assert eng.admit(req)
    page = eng.prefix.get(key).pages[0]
    assert eng.unregister_prefix(key)
    assert eng.prefix.get(key) is None           # new admits stop matching
    assert eng.allocator.refcount(page) == 1     # in-flight req still maps
    assert not eng.unregister_prefix(key)        # unknown key: no-op False
    for _ in range(8):
        if req.done:
            break
        eng.step()
    assert req.done
    assert eng.allocator.refcount(page) == 0     # last reference released
    assert eng.allocator.free_pages == eng.num_pages - 1


def test_shared_admits_varying_suffix_lengths_one_trace():
    """Suffixes of different lengths pad to one canonical width: every
    shared admit runs the same compiled continuation shape and still
    matches the unshared engine token-for-token."""
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(9)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    a = [Request(i, np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, size=tail)]), max_new=4)
        for i, tail in enumerate((1, 3, 5, 7))]
    b = [Request(r.rid, r.prompt.copy(), max_new=r.max_new) for r in a]

    plain = _mk_engine(cfg, prm)
    plain.run(a, max_steps=64)
    shared = _mk_engine(cfg, prm)
    shared.register_prefix(prefix)
    shared.run(b, max_steps=64)

    for ra, rb in zip(a, b):
        assert ra.done and rb.done
        assert ra.out == rb.out, (
            f"request {ra.rid} (suffix {len(ra.prompt) - 16}) diverged")
    assert shared.prefix_stats()["hits"] == 4
    if hasattr(shared._cont_prefill, "_cache_size"):
        assert shared._cont_prefill._cache_size() == 1


# ---------------------------------------------------------------------------
# allocator + engine state round-trips bit-exactly through snapshots
# ---------------------------------------------------------------------------

def _roundtrip_state(alloc):
    clone = PG.PageAllocator(alloc.num_pages)
    clone.load_state(alloc.state_dict())
    return clone


def test_allocator_state_roundtrip_deterministic():
    rng = np.random.default_rng(5)
    names = ["alloc", "share", "free"]
    for trial in range(10):
        ops = [(names[rng.integers(0, 3)], int(rng.integers(0, 100)))
               for _ in range(30)]
        alloc, _ = _drive_allocator(9, ops)
        clone = _roundtrip_state(alloc)
        assert clone.state_dict() == alloc.state_dict()
        # same future: both hand out identical pages in identical order
        assert clone.alloc(2) == alloc.alloc(2)
        assert clone.state_dict() == alloc.state_dict()


@settings(max_examples=50, deadline=None)
@given(ops=st.lists(st.tuples(
    st.sampled_from(["alloc", "share", "free"]),
    st.integers(min_value=0, max_value=99)), max_size=40))
def test_allocator_state_roundtrip_property(ops):
    alloc, _ = _drive_allocator(9, ops)
    clone = _roundtrip_state(alloc)
    assert clone.state_dict() == alloc.state_dict()
    assert clone.alloc(1) == alloc.alloc(1)
    assert clone.state_dict() == alloc.state_dict()


def test_allocator_load_state_size_mismatch_raises():
    alloc = PG.PageAllocator(9)
    with pytest.raises(ValueError, match="size mismatch"):
        PG.PageAllocator(5).load_state(alloc.state_dict())


def _chain_leaves(payload):
    """Leaves exactly as ``SnapshotStore.restore`` hands them back: the
    cache tree flattened to keystr-keyed host arrays."""
    flat, _ = jax.tree_util.tree_flatten_with_path(payload["cache"])
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in flat}


def test_hydrated_engine_decodes_in_lockstep():
    """from_snapshot restores pool + tables + allocator + prefixes +
    in-flight requests exactly: replica decode == producer decode with
    zero replica prefill."""
    cfg, prm = _mk("smollm-135m")
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, cfg.vocab_size, size=16)
    producer = _mk_engine(cfg, prm)
    producer.register_prefix(prefix)
    reqs = _mk_requests(cfg, rng, 3, prefix, max_new=12)
    for r in reqs:
        assert producer.admit(r)
    producer.step()                                  # mid-flight snapshot
    leaves = _chain_leaves(producer.snapshot_payload())

    replica = PG.PagedServingEngine.from_snapshot(cfg, prm, leaves)
    assert replica.allocator.state_dict() == producer.allocator.state_dict()
    assert replica.prefix.state_dict() == producer.prefix.state_dict()
    assert replica._chains == producer._chains
    assert replica.prefill_tokens == producer.prefill_tokens
    rep_reqs = [a for a in replica.active if a is not None]
    assert len(rep_reqs) == 3
    for ra, rb in zip(reqs, rep_reqs):
        assert ra.rid == rb.rid and ra.out == rb.out
        assert np.array_equal(ra.prompt, rb.prompt)

    pre = replica.prefill_tokens
    for _ in range(4):                               # lockstep decode
        producer.step()
        replica.step()
    for ra, rb in zip(reqs, rep_reqs):
        assert ra.out == rb.out, f"replica diverged on request {ra.rid}"
    assert replica.prefill_tokens == pre             # no replica prefill


def test_from_snapshot_requires_meta_leaf():
    cfg, prm = _mk("smollm-135m")
    eng = _mk_engine(cfg, prm)
    leaves = {k: v for k, v in _chain_leaves(eng.snapshot_payload()).items()
              if k != "['meta']"}
    with pytest.raises(KeyError, match="meta"):
        PG.PagedServingEngine.from_snapshot(cfg, prm, leaves)


def test_prefix_cache_state_roundtrip():
    cache = PX.PrefixCache()
    cache.add(PX.PrefixEntry(key="a", tokens=np.arange(16, dtype=np.int32),
                             pages=[1, 2]))
    cache.match(np.arange(20))                       # hit, bumps clock
    cache.match(np.arange(5, 25))                    # miss
    clone = PX.PrefixCache()
    clone.load_state(cache.state_dict())
    assert clone.state_dict() == cache.state_dict()
    e = clone.get("a")
    assert e is not None and e.pages == [1, 2]
    assert np.array_equal(e.tokens, np.arange(16))
    assert clone.stats() == cache.stats()
