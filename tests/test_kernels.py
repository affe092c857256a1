"""Pallas spectral-lossy kernels vs the pure-jnp oracle (ref.py).

Covers: shape/dtype sweeps, threshold-by-histogram ≡ threshold-by-sort
(paper finding F7's TPU replacement), error bounds, and hypothesis
properties of the end-to-end codec.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st   # optional-hypothesis shim

from repro.kernels import ops, ref
from repro.kernels import spectral_lossy as K


def _signal(n, seed=0, kind="smooth"):
    rng = np.random.default_rng(seed)
    t = np.linspace(0, 20, n)
    if kind == "smooth":
        x = np.sin(t) + 0.25 * np.sin(9 * t) + 0.02 * rng.standard_normal(n)
    elif kind == "noise":
        x = rng.standard_normal(n)
    else:  # spiky
        x = np.zeros(n)
        x[rng.integers(0, n, size=max(1, n // 50))] = rng.standard_normal(
            max(1, n // 50)) * 10
    return jnp.asarray(x.astype(np.float32))


# ---------------------------------------------------------------------------
# kernel vs oracle, shape sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 2048, 2048 + 256, 40000, 257])
@pytest.mark.parametrize("kind", ["smooth", "noise"])
def test_dct_hist_kernel_matches_oracle(n, kind):
    x = _signal(n, kind=kind)
    xb, _ = ref.blockize(x)
    pad = (-xb.shape[0]) % K.HIST_TILE
    xb = jnp.pad(xb, ((0, pad), (0, 0)))
    y_k, cnt_k, eng_k = K.dct_hist(xb, interpret=True)
    y_o = ref.dct_blocks(xb)
    cnt_o, eng_o = ref.energy_histogram(y_o)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt_k), np.asarray(cnt_o))
    np.testing.assert_allclose(np.asarray(eng_k), np.asarray(eng_o),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("nb", [8, 64, 72, 136])
def test_quant_dequant_kernels_match_oracle(nb):
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((nb, ref.BLOCK)).astype(np.float32))
    t = jnp.asarray(0.3, jnp.float32)
    q_k, s_k = K.threshold_quant(y, t, interpret=True)
    q_o, s_o = ref.quantize_blocks(y, t)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_o))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_o), rtol=1e-6)
    x_k = K.dequant_idct(q_k, s_k, interpret=True)
    x_o = ref.idct_blocks(ref.dequantize_blocks(q_o, s_o))
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_o),
                               rtol=1e-5, atol=1e-5)


def test_dct_hist_tiled_matches_accumulated_histogram():
    """Per-tile rows sum to the accumulated histogram of kernel 1."""
    x = _signal(40000)
    xb, _ = ref.blockize(x)
    pad = (-xb.shape[0]) % K.HIST_TILE
    xb = jnp.pad(xb, ((0, pad), (0, 0)))
    y_t, cnt_t, eng_t = K.dct_hist_tiled(xb, interpret=True)
    y_a, cnt_a, eng_a = K.dct_hist(xb, interpret=True)
    assert cnt_t.shape == (xb.shape[0] // K.HIST_TILE, ref.NBINS)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_a))
    np.testing.assert_allclose(np.asarray(cnt_t).sum(0), np.asarray(cnt_a))
    np.testing.assert_allclose(np.asarray(eng_t).sum(0), np.asarray(eng_a),
                               rtol=1e-5, atol=1e-6)


def test_threshold_quant_per_block_vector_matches_scalar_slices():
    """A vector of per-block thresholds ≡ scalar invocations per segment —
    the contract the fused multi-leaf dispatch relies on."""
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.standard_normal((24, ref.BLOCK)).astype(np.float32))
    t_vec = jnp.asarray(np.repeat([0.1, 0.5, 1.0], 8).astype(np.float32))
    q_v, s_v = K.threshold_quant(y, t_vec, interpret=True)
    for seg, t in enumerate([0.1, 0.5, 1.0]):
        sl = slice(seg * 8, (seg + 1) * 8)
        q_s, s_s = K.threshold_quant(y[sl], jnp.asarray(t), interpret=True)
        np.testing.assert_array_equal(np.asarray(q_v[sl]), np.asarray(q_s))
        np.testing.assert_array_equal(np.asarray(s_v[sl]), np.asarray(s_s))


def test_fused_packed_kernel_path_matches_per_leaf_kernels():
    """The TPU fused-tree recipe (packed dct_hist_tiled -> segment-summed
    histograms -> per-block-threshold quant), executed in interpret mode,
    reproduces the per-leaf kernel results bit-for-bit."""
    rng = np.random.default_rng(3)
    leaves = [jnp.asarray(rng.standard_normal(n).astype(np.float32))
              for n in (2048, 6000, 512)]
    eps = 1e-2
    blocks = []
    for x in leaves:
        xb, _ = ref.blockize(x)
        xb = jnp.pad(xb, ((0, (-xb.shape[0]) % K.HIST_TILE), (0, 0)))
        blocks.append(xb)
    counts = [b.shape[0] for b in blocks]
    packed = jnp.concatenate(blocks, 0)
    y, _, eng_t = K.dct_hist_tiled(packed, interpret=True)
    tile_seg = np.repeat(np.arange(len(counts)),
                         [c // K.HIST_TILE for c in counts])
    seg_eng = jnp.zeros((len(counts), ref.NBINS), jnp.float32
                        ).at[jnp.asarray(tile_seg)].add(eng_t)
    t_seg = jax.vmap(
        lambda e: ref.threshold_from_histogram(e, eps))(seg_eng)
    block_seg = np.repeat(np.arange(len(counts)), counts)
    q, s = K.threshold_quant(y, t_seg[jnp.asarray(block_seg)],
                             interpret=True)
    off = 0
    for xb, c in zip(blocks, counts):
        y_k, _, eng_k = K.dct_hist(xb, interpret=True)
        t_k = ref.threshold_from_histogram(eng_k, eps)
        q_k, s_k = K.threshold_quant(y_k, t_k, interpret=True)
        np.testing.assert_array_equal(np.asarray(q[off:off + c]),
                                      np.asarray(q_k))
        np.testing.assert_array_equal(np.asarray(s[off:off + c]),
                                      np.asarray(s_k))
        off += c


def test_fused_tree_trace_cache_buckets_shapes():
    """Elastic-mesh contract: leaf resizes that stay inside the same
    power-of-two block bucket reuse the compiled fused kernel instead of
    re-tracing (ROADMAP perf candidate), and stay bit-identical to the
    per-leaf path."""
    policy = lambda k: True    # noqa: E731 - compress every leaf

    # 6100 elems -> 24 blocks -> bucket 32; 8100 elems -> 32 blocks -> 32.
    # Without bucketing these are distinct trace keys (24 vs 32 rows).
    t1 = {"a": jnp.ones(6100, jnp.float32),
          "b": jnp.ones((40, 40), jnp.float32)}
    t2 = {"a": jnp.ones(8100, jnp.float32),
          "b": jnp.ones((41, 40), jnp.float32)}
    ops.spectral_compress_tree(t1, 1e-2, policy)
    size_after_first = ops.packed_tree_cache_size()
    ops.spectral_compress_tree(t2, 1e-2, policy)
    assert ops.packed_tree_cache_size() == size_after_first, \
        "same pow2 buckets must not re-trace the fused tree kernel"

    # a genuinely new bucket (crossing a pow2 boundary) does compile
    t3 = {"a": jnp.ones(20000, jnp.float32),     # 79 blocks -> bucket 128
          "b": jnp.ones((41, 40), jnp.float32)}
    ops.spectral_compress_tree(t3, 1e-2, policy)
    assert ops.packed_tree_cache_size() == size_after_first + 1

    # bucketed fused output stays bit-identical to the per-leaf path
    rng = np.random.default_rng(11)
    t4 = {"a": jnp.asarray(rng.standard_normal(6100).astype(np.float32)),
          "b": jnp.asarray(rng.standard_normal((40, 40))
                           .astype(np.float32))}
    fused = ops.spectral_compress_tree(t4, 1e-2, policy, fused=True)
    plain = ops.spectral_compress_tree(t4, 1e-2, policy, fused=False)
    for key in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(fused[key].q),
                                      np.asarray(plain[key].q))
        np.testing.assert_array_equal(np.asarray(fused[key].scale),
                                      np.asarray(plain[key].scale))


def test_grouped_launches_bit_identical_to_one_launch(monkeypatch):
    """A tree over the launch budget compresses in several fused launches,
    each under it, bit-identical leaf by leaf to the single launch."""
    rng = np.random.default_rng(5)
    state = {"w": jnp.ones((8, 8), jnp.float32),
             "opt": {f"m{i}": jnp.asarray(rng.standard_normal(n)
                                          .astype(np.float32))
                     for i, n in enumerate((5000, 20000, 777, 9000, 3000))}}
    policy = lambda k: "opt" in k   # noqa: E731
    assert ops.compress_launches(state, policy) == 1
    one = ops.spectral_compress_tree(state, 1e-2, policy)
    budget = 128 * ref.BLOCK * 4
    monkeypatch.setattr(ops, "COMPRESS_LAUNCH_BYTES", budget)
    launched, packed = [], ops._compress_tree_packed

    def count(blocks, *args):
        launched.append(sum(b.size for b in blocks) * 4)
        return packed(blocks, *args)

    monkeypatch.setattr(ops, "_compress_tree_packed", count)
    grouped = ops.spectral_compress_tree(state, 1e-2, policy)
    assert len(launched) == ops.compress_launches(state, policy) >= 3
    assert max(launched) <= budget
    for key, c in one["opt"].items():
        g = grouped["opt"][key]
        np.testing.assert_array_equal(np.asarray(g.q), np.asarray(c.q))
        np.testing.assert_array_equal(np.asarray(g.scale),
                                      np.asarray(c.scale))
    assert grouped["w"] is state["w"]


def test_launch_budget_keeps_smollm_in_one_launch():
    """smollm-135m's moments compress in one launch; the one-chip cut of
    Moonlight-16B-A3B (4.5 GB of f32 blocks) does not."""
    from repro.checkpoint.manager import default_lossy_policy
    from repro.configs import base
    from repro.launch import train
    small = train.state_spec(base.get("smollm-135m"))
    assert ops.compress_launches(small, default_lossy_policy) == 1
    cut = base.chip_share(base.get("moonlight-16b-a3b"), n_layers=5,
                          n_held=8, vocab_size=20480)
    assert ops.compress_launches(train.state_spec(cut),
                                 default_lossy_policy) > 1


def test_fused_tree_bit_equal_to_per_leaf():
    """Tentpole contract: the single-dispatch fused tree compression is
    bit-identical to the per-leaf path, leaf by leaf."""
    rng = np.random.default_rng(4)
    state = {
        "w": jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32)),
        "opt": {"mu": jnp.asarray(rng.standard_normal(5000)
                                  .astype(np.float32)),
                "nu": jnp.asarray(rng.standard_normal((16, 100))
                                  .astype(np.float32)),
                "mu_b": jnp.asarray(rng.standard_normal(77)
                                    .astype(np.float32))},
    }
    policy = lambda k: "mu" in k or "nu" in k   # noqa: E731
    fused = ops.spectral_compress_tree(state, 1e-2, policy, fused=True)
    plain = ops.spectral_compress_tree(state, 1e-2, policy, fused=False)
    for key in ("mu", "nu", "mu_b"):
        f, p = fused["opt"][key], plain["opt"][key]
        np.testing.assert_array_equal(np.asarray(f.q), np.asarray(p.q))
        np.testing.assert_array_equal(np.asarray(f.scale),
                                      np.asarray(p.scale))
        assert (f.n_elements, f.shape, f.dtype) == \
            (p.n_elements, p.shape, p.dtype)
    # non-selected leaves pass through untouched
    assert fused["w"] is state["w"]
    # roundtrip still honors the codec's error bound
    back = ops.spectral_decompress(fused["opt"]["mu"])
    assert ref.rel_l2_error(state["opt"]["mu"], back) \
        <= ref.error_bound(1e-2) + 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_codec_dtype_sweep(dtype):
    x = _signal(5000).astype(dtype)
    c = ops.spectral_compress(x, 1e-2)
    xh = ops.spectral_decompress(c)
    assert xh.dtype == dtype and xh.shape == x.shape
    err = ref.rel_l2_error(x.astype(jnp.float32), xh.astype(jnp.float32))
    assert err <= ref.error_bound(1e-2) + 0.02  # + dtype rounding slack


# ---------------------------------------------------------------------------
# histogram select ≡ sort select (the F7 TPU adaptation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eps", [1e-1, 1e-2, 1e-3])
@pytest.mark.parametrize("kind", ["smooth", "noise", "spiky"])
def test_histogram_select_equals_sort_select(eps, kind):
    x = _signal(30000, seed=3, kind=kind)
    xb, _ = ref.blockize(x)
    y = ref.dct_blocks(xb)
    _, energies = ref.energy_histogram(y)
    t_hist = ref.threshold_from_histogram(energies, eps)
    t_sort = ref.threshold_by_sort(y, eps)
    total = float(jnp.sum(y * y))
    a = np.abs(np.asarray(y)).reshape(-1)
    dropped_hist = float(np.sum((a[a < float(t_hist)]) ** 2))
    # guarantee: histogram threshold never discards more than the budget
    assert dropped_hist <= (eps * eps) * total * (1 + 1e-5)
    # conservatism: within one bin resolution of the sort-optimal threshold
    if float(t_sort) > 0 and float(t_hist) > 0:
        ratio = float(t_hist) / float(t_sort)
        assert ratio <= 2 ** (80.0 / ref.NBINS) + 1e-6  # one bin width
    kept_hist = float(np.mean(a >= float(t_hist)))
    kept_sort = float(np.mean(a >= float(t_sort)))
    assert kept_hist >= kept_sort - 1e-9  # never keeps fewer than optimal


# ---------------------------------------------------------------------------
# error-bound property (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=16, max_value=4096),
    seed=st.integers(min_value=0, max_value=2 ** 16),
    eps=st.sampled_from([1e-1, 1e-2, 1e-3]),
    scale=st.floats(min_value=1e-3, max_value=1e3),
)
def test_roundtrip_error_bound_property(n, seed, eps, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.standard_normal(n) * scale).astype(np.float32))
    c = ref.compress(x, eps)
    xh = ref.decompress(c)
    assert ref.rel_l2_error(x, xh) <= ref.error_bound(eps) + 1e-5
    assert not np.isnan(np.asarray(xh)).any()


@settings(max_examples=10, deadline=None)
@given(shape=st.sampled_from([(7,), (33, 5), (4, 4, 17), (256,), (2, 128)]))
def test_shape_preservation_property(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    c = ops.spectral_compress(x, 1e-2)
    xh = ops.spectral_decompress(c)
    assert xh.shape == tuple(shape)


def test_compression_ratio_on_smooth_data_matches_paper():
    """Paper §IV-B: lossy+lossless removes ~98% at eps=1e-2 on smooth fields."""
    from repro.core import codecs
    x = _signal(200_000, kind="smooth")
    c = ops.spectral_compress(x, 1e-2)
    blob, st_ = codecs.encode(np.asarray(c.q), "zlib")
    stored = len(blob) + int(np.asarray(c.scale).nbytes)
    ratio = (x.nbytes - stored) / x.nbytes
    assert ratio >= 0.95, f"only {ratio:.3f} removed"


def test_zero_input_exact():
    x = jnp.zeros(1000)
    xh = ops.spectral_decompress(ops.spectral_compress(x, 1e-2))
    np.testing.assert_array_equal(np.asarray(xh), np.asarray(x))


def test_constant_input_block_aligned_exact():
    # a constant block is pure DC -> survives any threshold, exact to quant
    x = jnp.full((1024,), 3.25)   # 4 whole blocks, no zero-padding
    xh = ops.spectral_decompress(ops.spectral_compress(x, 1e-2))
    np.testing.assert_allclose(np.asarray(xh), np.asarray(x), atol=0.02)


def test_constant_input_padded_l2_bound():
    # zero-padding makes the tail block a step function (Gibbs ringing);
    # the codec's guarantee is relative-L2, which must still hold
    x = jnp.full((1000,), 3.25)
    c = ops.spectral_compress(x, 1e-2)
    xh = ops.spectral_decompress(c)
    assert ref.rel_l2_error(x, xh) <= ref.error_bound(1e-2)


# ---------------------------------------------------------------------------
# two-level histogram selection (coarse 32 + refine 16)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["smooth", "noise", "spiky"])
@pytest.mark.parametrize("eps", [1e-3, 1e-2, 1e-1, 0.5])
def test_two_level_selector_matches_flat(kind, eps):
    """The coarse+refine selector picks the same quantized bin edge as the
    flat 512-bin selector — the invariant that keeps spectral_compress
    outputs bit-identical across the kernel rework."""
    y = ref.dct_blocks(ref.blockize(_signal(40000, kind=kind))[0])
    _, energies = ref.energy_histogram(y)
    t_flat = ref.threshold_from_histogram(energies, eps)
    t_two = ref.threshold_two_level(y, eps)
    np.testing.assert_array_equal(np.asarray(t_flat), np.asarray(t_two))


@pytest.mark.parametrize("selector", ["histogram", "two_level"])
@pytest.mark.parametrize("case", ["eps_ge_1", "zeros", "single_block"])
def test_selector_edge_cases(selector, case):
    if case == "eps_ge_1":
        x, eps = _signal(4096, kind="noise"), 1.5     # budget >= total energy
    elif case == "zeros":
        x, eps = jnp.zeros(4096), 1e-2
    else:
        x, eps = _signal(ref.BLOCK, kind="smooth"), 1e-2   # exactly one block
    c = ref.compress(x, eps, selector=selector)
    base = ref.compress(x, eps)                       # flat selector
    np.testing.assert_array_equal(np.asarray(c.q), np.asarray(base.q))
    np.testing.assert_array_equal(np.asarray(c.scale), np.asarray(base.scale))
    if case in ("eps_ge_1", "zeros"):
        # drop-everything / no-energy: every coefficient must be zeroed
        assert not np.asarray(c.q).any()


def test_coarse_and_refine_kernels_match_oracle():
    x = _signal(40000, kind="noise")
    xb, _ = ref.blockize(x)
    xb = jnp.pad(xb, ((0, (-xb.shape[0]) % K.HIST_TILE), (0, 0)))
    y_k, cnt_k, eng_k = K.dct_hist_coarse(xb, interpret=True)
    y_o = ref.dct_blocks(xb)
    cnt_o, eng_o = ref.coarse_energy_histogram(y_o)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt_k), np.asarray(cnt_o))
    np.testing.assert_allclose(np.asarray(eng_k), np.asarray(eng_o),
                               rtol=1e-4, atol=1e-6)
    _, cc, _, _ = ref.select_coarse(eng_o, 1e-2)
    fcnt_k, feng_k = K.hist_refine(y_o, cc, interpret=True)
    fcnt_o, feng_o = ref.refine_energy_histogram(y_o, cc)
    np.testing.assert_allclose(np.asarray(fcnt_k), np.asarray(fcnt_o))
    np.testing.assert_allclose(np.asarray(feng_k), np.asarray(feng_o),
                               rtol=1e-4, atol=1e-6)


def test_kernel_two_level_path_threshold_equals_flat():
    """The full kernel recipe (coarse kernel -> select_coarse -> refine
    kernel -> select_fine) lands on the flat selector's threshold exactly."""
    x = _signal(40000, kind="smooth")
    xb, _ = ref.blockize(x)
    xb = jnp.pad(xb, ((0, (-xb.shape[0]) % K.HIST_TILE), (0, 0)))
    eps = 1e-2
    y, _, ce = K.dct_hist_coarse(xb, interpret=True)
    c, cc, base, budget = ref.select_coarse(ce, eps)
    _, fe = K.hist_refine(y, cc, interpret=True)
    t_two = ref.select_fine(fe, c, cc, base, budget)
    _, energies = K.dct_hist(xb, interpret=True)[1:]
    t_flat = ref.threshold_from_histogram(energies, eps)
    np.testing.assert_array_equal(np.asarray(t_two), np.asarray(t_flat))


def test_tiled_rows_segment_sum_parity_with_accumulated():
    """dct_hist's grid accumulation vs dct_hist_tiled rows segment-summed —
    the invariant _compress_tree_packed relies on but never asserts
    directly. y and the (integer-valued) counts must match BITWISE; the
    energy sums may differ by an ulp per bin (the accumulating kernel fuses
    ``+=`` into the dot_general reduction, so its fp association is not an
    ordered sum of the rounded tile partials), so the bit-identity boundary
    the fused tree path actually depends on is the *selected threshold* —
    asserted bitwise across an eps sweep."""
    x = _signal(64 * ref.BLOCK, kind="noise")
    xb, _ = ref.blockize(x)
    y_t, cnt_t, eng_t = K.dct_hist_tiled(xb, interpret=True)
    y_a, cnt_a, eng_a = K.dct_hist(xb, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_a))
    cnt_seq = np.zeros(ref.NBINS, np.float32)
    eng_seq = np.zeros(ref.NBINS, np.float32)
    for row in range(eng_t.shape[0]):        # same order as the grid walks
        cnt_seq = cnt_seq + np.asarray(cnt_t[row])
        eng_seq = eng_seq + np.asarray(eng_t[row])
    np.testing.assert_array_equal(cnt_seq, np.asarray(cnt_a))
    np.testing.assert_allclose(eng_seq, np.asarray(eng_a), rtol=1e-6)
    seg = jnp.sum(eng_t, axis=0)             # what the fused path feeds in
    for eps in (1e-3, 1e-2, 1e-1, 0.5):
        t_seg = ref.threshold_from_histogram(seg, eps)
        t_acc = ref.threshold_from_histogram(eng_a, eps)
        np.testing.assert_array_equal(np.asarray(t_seg), np.asarray(t_acc))


# ---------------------------------------------------------------------------
# kernel-layer bugfixes: prime block counts + loud shape errors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb", [7, 13, 97])
def test_prime_block_count_uses_full_tile_and_roundtrips(nb):
    """Prime-sized leaves used to degrade to tile=1 (an nb-step grid of
    single-block launches); now the buffer is padded to the tile multiple
    and sliced back, keeping a full-width tile."""
    tile, pad = K._tile_and_pad(nb, K.QUANT_TILE)
    assert tile == min(K.QUANT_TILE, nb) and tile > 1
    assert (nb + pad) % tile == 0
    rng = np.random.default_rng(nb)
    y = jnp.asarray(rng.standard_normal((nb, ref.BLOCK)).astype(np.float32))
    t = jnp.asarray(0.3, jnp.float32)
    q_k, s_k = K.threshold_quant(y, t, interpret=True)
    q_o, s_o = ref.quantize_blocks(y, t)
    assert q_k.shape == (nb, ref.BLOCK)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_o))
    # scale: amax/127 may fuse to a reciprocal multiply inside the kernel
    # (1-ulp wobble, independent of padding) — oracle parity is 1e-6
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_o), rtol=1e-6)
    # the padding itself must be transparent: manually pre-padding to the
    # tile multiple and slicing must reproduce the internal path BITWISE
    pad = (-nb) % tile
    y_pad = jnp.pad(y, ((0, pad), (0, 0)))
    q_p, s_p = K.threshold_quant(y_pad, t, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_p[:nb]), np.asarray(q_k))
    np.testing.assert_array_equal(np.asarray(s_p[:nb]), np.asarray(s_k))
    x_k = K.dequant_idct(q_k, s_k, interpret=True)
    x_p = K.dequant_idct(q_p, s_p, interpret=True)
    np.testing.assert_array_equal(np.asarray(x_p[:nb]), np.asarray(x_k))
    x_o = ref.idct_blocks(ref.dequantize_blocks(q_o, s_o))
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_o),
                               rtol=1e-5, atol=1e-5)


def test_hist_kernels_raise_valueerror_on_bad_shapes():
    with pytest.raises(ValueError, match="multiple"):
        K.dct_hist(jnp.zeros((7, ref.BLOCK)), interpret=True)
    with pytest.raises(ValueError, match="blocked buffer"):
        K.dct_hist(jnp.zeros((8, 128)), interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        K.dct_hist_tiled(jnp.zeros((9, ref.BLOCK)), interpret=True)
    with pytest.raises(ValueError, match="expected"):
        K.threshold_quant(jnp.zeros((4, 128)), jnp.asarray(0.1),
                          interpret=True)
