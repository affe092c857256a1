"""Round-trip property suite over EVERY codec in the compression registry.

Two layers, one contract:

  * deterministic parametrized coverage of the named payload classes
    (empty, 1-byte, incompressible-random, highly-repetitive,
    larger-than-chunk) for every registered codec — always runs;
  * hypothesis-randomized round-trips (via the optional ``_hyp`` shim):
    arbitrary payloads through the lossless codecs, randomized base/target
    pairs through the ``delta`` codec, and v1/v2 frame cross-decoding.

Lossless codecs (including ``delta``, which self-contains when encoded
without a base) must round-trip bit-exactly with shape+dtype preserved;
lossy codecs must preserve shape and honour their declared
``error_bound()`` (relative L2).
"""
import struct

import numpy as np
import pytest
from _hyp import given, settings, st   # optional-hypothesis shim

from repro.core import codecs, compression, delta


def _payload(kind: str, *, floats: bool, seed: int = 0) -> np.ndarray:
    """The named payload classes; ``floats`` picks the float32 variants
    (lossy codecs are defined over float data)."""
    rng = np.random.default_rng(seed)
    if kind == "empty":
        return np.empty((0,), np.float32 if floats else np.int8)
    if kind == "one-byte":
        if floats:
            return np.asarray([2.5], np.float32)      # one-element payload
        return np.asarray([7], np.int8)               # literally one byte
    if kind == "incompressible":
        if floats:
            return rng.standard_normal(4096).astype(np.float32)
        return rng.integers(-128, 128, size=16384).astype(np.int8)
    if kind == "repetitive":
        if floats:
            return np.tile(np.linspace(0, 1, 32, dtype=np.float32), 512)
        return np.tile(np.arange(16, dtype=np.int8), 1024)
    if kind == "larger-than-chunk":
        # > DEFAULT_CHUNK (1 MiB) raw bytes => multi-chunk frame
        n = (codecs.DEFAULT_CHUNK // 4) + 4096
        return rng.standard_normal(n).astype(np.float32)
    raise KeyError(kind)


PAYLOAD_KINDS = ("empty", "one-byte", "incompressible", "repetitive",
                 "larger-than-chunk")


def _rel_l2(out: np.ndarray, ref: np.ndarray) -> float:
    denom = float(np.linalg.norm(ref.astype(np.float64).ravel()))
    if denom == 0.0:
        return 0.0
    return float(np.linalg.norm(
        out.astype(np.float64).ravel()
        - ref.astype(np.float64).ravel())) / denom


@pytest.mark.parametrize("kind", PAYLOAD_KINDS)
@pytest.mark.parametrize("name", compression.available())
def test_registry_roundtrip_payload_classes(name, kind):
    codec = compression.get(name)
    arr = _payload(kind, floats=codec.lossy)
    out = codec.decode(codec.encode(arr))
    assert out.shape == arr.shape
    if not codec.lossy:
        np.testing.assert_array_equal(out, arr)
        assert out.dtype == arr.dtype
    else:
        assert _rel_l2(out, arr) <= codec.error_bound()


@pytest.mark.parametrize("kind", PAYLOAD_KINDS)
def test_delta_roundtrip_against_base_payload_classes(kind):
    """Every payload class as a (base, target) pair: mutate a slice of the
    base and delta-encode the result against it."""
    base = _payload(kind, floats=True)
    target = base.copy()
    if target.size:
        target[: max(1, target.size // 8)] += 1.0
    blob, stats = delta.encode(target, base, chunk_bytes=1 << 12)
    needs = delta.frame_needs_base(blob)
    out = delta.decode(blob, base if needs else None)
    np.testing.assert_array_equal(out, target)
    assert out.dtype == target.dtype
    assert stats.raw_bytes == target.nbytes


# -- hypothesis-randomized round-trips ---------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=4000),
    seed=st.integers(min_value=0, max_value=999),
    codec=st.sampled_from(["zlib", "zlib1", "bz2", "lzma", "none", "delta"]),
    chunk=st.sampled_from([257, 1 << 12, codecs.DEFAULT_CHUNK]),
)
def test_lossless_roundtrip_property(n, seed, codec, chunk):
    """Any byte payload, any chunking, through every lossless codec
    (``delta`` encodes self-contained here: no base)."""
    r = np.random.default_rng(seed)
    arr = r.integers(-128, 127, size=n).astype(np.int8)
    if codec == "delta":
        out = delta.decode(delta.encode(arr, None, chunk_bytes=chunk)[0])
    else:
        out = codecs.decode(codecs.encode(arr, codec, chunk_bytes=chunk)[0])
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == arr.dtype


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=5000),
    seed=st.integers(min_value=0, max_value=999),
    frac=st.floats(min_value=0.0, max_value=1.0),
    same_size=st.booleans(),
    chunk=st.sampled_from([257, 1 << 12]),
)
def test_delta_base_target_pairs_property(n, seed, frac, same_size, chunk):
    """Randomized base/target pairs: a mutated slice (append-mostly when
    ``frac`` is small, full rewrite at 1.0), and size-mismatched bases
    (which must fall back to self-contained frames)."""
    r = np.random.default_rng(seed)
    base = r.integers(-128, 127, size=n).astype(np.int8)
    if same_size:
        target = base.copy()
        k = int(n * frac)
        if k:
            target[n - k:] = r.integers(-128, 127, size=k)
    else:
        target = r.integers(-128, 127, size=n + 17).astype(np.int8)
    blob, stats = delta.encode(target, base, chunk_bytes=chunk)
    if delta.frame_needs_base(blob):
        out = delta.decode(blob, base)
        # a frame that references its base must refuse the wrong one
        with pytest.raises(delta.DeltaBaseMismatch):
            delta.decode(blob, None)
        if n:
            with pytest.raises(delta.DeltaBaseMismatch):
                delta.decode(blob, np.zeros(n + 3, np.int8))
    else:
        out = delta.decode(blob)
    np.testing.assert_array_equal(out, target)
    assert stats.n_copy + stats.n_xor + stats.n_self == -(-target.nbytes
                                                          // chunk)


def test_delta_bfloat16_dtype_token_roundtrip():
    """bfloat16's np.dtype .str is a void token ('<V2'); the delta frame
    must record it by name and restore the real dtype."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    base = rng.standard_normal(1000).astype(ml_dtypes.bfloat16)
    target = base.copy()
    target[:64] = rng.standard_normal(64)
    blob, _ = delta.encode(target, base, chunk_bytes=256)
    out = delta.decode(blob, base)
    assert out.dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(out.view(np.uint16),
                                  target.view(np.uint16))
    out = delta.decode(delta.encode(target)[0])      # self-contained too
    assert out.dtype == ml_dtypes.bfloat16


def test_codecs_bfloat16_dtype_token_roundtrip():
    """The shared lossless framing records extension dtypes by name too."""
    import ml_dtypes

    rng = np.random.default_rng(1)
    arr = rng.standard_normal(2048).astype(ml_dtypes.bfloat16)
    out = codecs.decode(codecs.encode(arr, "zlib")[0])
    assert out.dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(out.view(np.uint16), arr.view(np.uint16))


def _encode_v1(arr: np.ndarray, codec: str = "zlib") -> bytes:
    """The legacy pre-chunking frame layout, byte-for-byte."""
    import zlib as _zlib
    comp = {"zlib": lambda b: _zlib.compress(b, 6),
            "none": lambda b: b}[codec]
    cid = {"zlib": 1, "none": 0}[codec]
    arr = np.ascontiguousarray(arr)
    raw = arr.tobytes()
    dt = np.dtype(arr.dtype).str.encode()
    return (codecs.MAGIC + struct.pack("<BBB", 1, cid, len(dt)) + dt
            + struct.pack("<B", arr.ndim)
            + struct.pack(f"<{arr.ndim}q", *arr.shape)
            + struct.pack("<q", len(raw)) + comp(raw))


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=4000),
    seed=st.integers(min_value=0, max_value=999),
    codec=st.sampled_from(["zlib", "none"]),
)
def test_v1_v2_frame_cross_decoding_property(n, seed, codec):
    """The same array through the legacy v1 frame and the chunked v2 frame
    must decode identically (old snapshots/checkpoints restore unchanged)."""
    r = np.random.default_rng(seed)
    arr = (r.standard_normal(n) * 50).astype(np.float32)
    from_v1 = codecs.decode(_encode_v1(arr, codec))
    from_v2 = codecs.decode(codecs.encode(arr, codec, chunk_bytes=1 << 12)[0])
    np.testing.assert_array_equal(from_v1, from_v2)
    np.testing.assert_array_equal(from_v1, arr)
    assert from_v1.dtype == from_v2.dtype == arr.dtype


# ---------------------------------------------------------------------------
# two-level threshold selection: bin-edge identity on every payload class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", PAYLOAD_KINDS)
@pytest.mark.parametrize("eps", [1e-3, 1e-2, 1e-1, 1.0, 2.0])
def test_two_level_selector_bin_edge_identical_payload_classes(kind, eps):
    """The coarse-32 + refine-16 selector must pick the same quantized bin
    edge as the flat 512-bin selector on every payload class (including
    eps >= 1 drop-everything), so spectral_compress outputs stay
    bit-identical across the kernel rework."""
    import jax.numpy as jnp

    from repro.kernels import ref as kref

    arr = _payload(kind, floats=True)
    if arr.size == 0:
        pytest.skip("blockize is undefined for empty tensors")
    x = jnp.asarray(arr)
    y = kref.dct_blocks(kref.blockize(x)[0])
    _, energies = kref.energy_histogram(y)
    t_flat = kref.threshold_from_histogram(energies, eps)
    t_two = kref.threshold_two_level(y, eps)
    np.testing.assert_array_equal(np.asarray(t_flat), np.asarray(t_two))
    c_flat = kref.compress(x, eps)
    c_two = kref.compress(x, eps, selector="two_level")
    np.testing.assert_array_equal(np.asarray(c_flat.q), np.asarray(c_two.q))
    np.testing.assert_array_equal(np.asarray(c_flat.scale),
                                  np.asarray(c_two.scale))


# ---------------------------------------------------------------------------
# streamed chunk-aligned lossy framing == monolithic framing, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [300,                       # single chunk
                               (1 << 20) + 70_000])       # multi-chunk q
def test_streamed_chunked_lossy_frame_byte_identical(n):
    """The fused quantize+chunking path (device-sliced q chunks framed as
    they land) must produce the exact bytes of the monolithic path — the
    frame is the checkpoint wire format, so this is a hard contract."""
    import jax.numpy as jnp

    from repro.core import lossy

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    blob_plain, st_plain = lossy.compress_tensor(x, 1e-2, lossless="zstd",
                                                   stream=False)
    blob_stream, st_stream = lossy.compress_tensor(x, 1e-2, lossless="zstd",
                                                     stream=True)
    assert blob_stream == blob_plain
    assert st_stream == st_plain
    pool = codecs.codec_pool()
    blob_pool, _ = lossy.compress_tensor(x, 1e-2, lossless="zstd",
                                         stream=True, pool=pool)
    assert blob_pool == blob_plain
    rt = np.asarray(lossy.decompress_tensor(blob_stream))
    rt_plain = np.asarray(lossy.decompress_tensor(blob_plain))
    np.testing.assert_array_equal(rt, rt_plain)


def test_assemble_frame_matches_encode():
    """assemble_frame over self-compressed chunk payloads reproduces
    encode()'s frame bytes exactly."""
    rng = np.random.default_rng(6)
    arr = rng.integers(-120, 120, size=300_000).astype(np.int8)
    chunk = 1 << 16
    blob, _ = codecs.encode(arr, "zlib", chunk_bytes=chunk)
    _, comp, _ = codecs.compressor("zlib")
    mv = memoryview(arr)
    payloads = [comp(mv[o:o + chunk]) for o in range(0, arr.nbytes, chunk)]
    rebuilt = codecs.assemble_frame("zlib", arr.dtype, arr.shape,
                                    arr.nbytes, chunk, payloads)
    assert rebuilt == blob
    with pytest.raises(KeyError):
        codecs.compressor("nope")
