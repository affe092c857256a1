"""Lossless codec layer: framing, roundtrips, Table II-style ratios."""
import numpy as np
import pytest
import zstandard
from _hyp import given, settings, st   # optional-hypothesis shim

from repro.core import codecs


@pytest.mark.parametrize("codec", codecs.available())
@pytest.mark.parametrize("dtype", [np.float32, np.int8, np.uint16, np.int64])
def test_roundtrip_all_codecs(codec, dtype, rng):
    arr = (rng.standard_normal((37, 21)) * 100).astype(dtype)
    blob, stats = codecs.encode(arr, codec)
    out = codecs.decode(blob)
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == arr.dtype
    assert stats.raw_bytes == arr.nbytes


def test_frame_self_describing(rng):
    arr = rng.standard_normal((3, 4, 5)).astype(np.float32)
    blob, _ = codecs.encode(arr, "bz2")
    out = codecs.decode(blob)   # no out-of-band metadata
    assert out.shape == (3, 4, 5)


def test_bad_magic_rejected():
    with pytest.raises(ValueError):
        codecs.decode(b"XXXX" + b"\x00" * 32)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=3000),
    seed=st.integers(min_value=0, max_value=999),
    codec=st.sampled_from(["zlib", "bz2", "lzma", "none"]),
)
def test_roundtrip_property(n, seed, codec):
    r = np.random.default_rng(seed)
    arr = r.integers(-128, 127, size=n).astype(np.int8)
    out = codecs.decode(codecs.encode(arr, codec)[0])
    np.testing.assert_array_equal(out, arr)


def test_table2_ordering_on_float_data(rng):
    """Paper Table II: plain lossless on float scientific data removes only
    a few percent; zeros-heavy int8 (post-lossy) compresses drastically."""
    floats = rng.standard_normal(200_000).astype(np.float32)
    sparse = np.zeros(200_000, np.int8)
    sparse[rng.integers(0, 200_000, 4000)] = rng.integers(-127, 127, 4000)
    for codec in ("zlib", "bz2", "lzma"):
        cr_float = codecs.compression_ratio(floats, codec).ratio
        cr_sparse = codecs.compression_ratio(sparse, codec).ratio
        assert cr_float < 0.2, f"{codec} on random floats: {cr_float}"
        assert cr_sparse > 0.9, f"{codec} on sparse int8: {cr_sparse}"


def test_compression_stats_eq1():
    s = codecs.CompressionStats("zlib", 100, 25)
    assert s.ratio == pytest.approx(0.75)   # paper Eq. (1)


# -- chunked v2 framing -------------------------------------------------------

def _encode_v1(arr, codec="zlib"):
    """The pre-chunking frame layout, byte-for-byte (old checkpoints)."""
    import struct
    import zlib as _zlib
    comp = {"zlib": lambda b: _zlib.compress(b, 6), "none": lambda b: b}[codec]
    cid = {"zlib": 1, "none": 0}[codec]
    arr = np.ascontiguousarray(arr)
    raw = arr.tobytes()
    dt = np.dtype(arr.dtype).str.encode()
    return (codecs.MAGIC + struct.pack("<BBB", 1, cid, len(dt)) + dt
            + struct.pack("<B", arr.ndim)
            + struct.pack(f"<{arr.ndim}q", *arr.shape)
            + struct.pack("<q", len(raw)) + comp(raw))


@pytest.mark.parametrize("codec", ["zlib", "none"])
def test_v1_frame_backward_compat_decode(codec, rng):
    arr = rng.standard_normal((100, 7)).astype(np.float32)
    out = codecs.decode(_encode_v1(arr, codec))
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == arr.dtype


@pytest.mark.parametrize("codec", ["zlib1", "bz2", "none"])
def test_multichunk_roundtrip(codec, rng):
    """A >1-chunk array: independent chunks reassemble exactly."""
    arr = rng.standard_normal(300_000).astype(np.float32)   # 1.2 MB
    blob, stats = codecs.encode(arr, codec, chunk_bytes=1 << 18)  # 5 chunks
    assert stats.raw_bytes == arr.nbytes
    out = codecs.decode(blob)
    np.testing.assert_array_equal(out, arr)


def test_chunk_pool_produces_identical_frames(rng):
    arr = rng.standard_normal(200_000).astype(np.float32)
    serial, _ = codecs.encode(arr, "zlib1", chunk_bytes=1 << 17)
    parallel, _ = codecs.encode(arr, "zlib1", chunk_bytes=1 << 17,
                                pool=codecs.codec_pool())
    assert serial == parallel               # pool changes time, not bytes
    out = codecs.decode(parallel, pool=codecs.codec_pool())
    np.testing.assert_array_equal(out, arr)


def test_chunked_frame_zero_size_and_0d(rng):
    empty = np.empty((0, 4), np.float32)
    out = codecs.decode(codecs.encode(empty, "zlib")[0])
    assert out.shape == (0, 4) and out.dtype == np.float32
    scalar = np.asarray(3.5, np.float32)
    out = codecs.decode(codecs.encode(scalar, "zlib")[0])
    # ascontiguousarray promotes 0-d to (1,) — same contract as v1 frames
    assert out.shape == (1,) and out[0] == np.float32(3.5)


def test_truncated_chunk_table_rejected(rng):
    """A chunk table that cannot cover raw_nbytes must raise, not decode a
    silently zero-filled tail (the v1 'frame length mismatch' guarantee)."""
    import struct
    arr = rng.standard_normal(200_000).astype(np.float32)   # 800 KB
    blob, _ = codecs.encode(arr, "zlib1", chunk_bytes=1 << 18)  # 4 chunks
    # header: MAGIC(4) ver/cid/dtlen(3) dt(3) ndim(1) shape(8) -> offset 19
    off = 4 + 3 + np.dtype(np.float32).str.encode().__len__() + 1 + 8
    raw_nbytes, chunk_bytes, n_chunks = struct.unpack_from("<qqI", blob, off)
    assert n_chunks == 4
    bad = bytearray(blob)
    struct.pack_into("<qqI", bad, off, raw_nbytes, chunk_bytes, 1)
    with pytest.raises(ValueError, match="chunk table"):
        codecs.decode(bytes(bad))


def test_unsupported_version_rejected(rng):
    arr = rng.standard_normal(16).astype(np.float32)
    blob, _ = codecs.encode(arr, "zlib")
    bad = blob[:4] + bytes([99]) + blob[5:]
    with pytest.raises(ValueError, match="version"):
        codecs.decode(bad)


# -- zstd: the checkpoint's lossless codec ------------------------------------

def _fresh_zstd_frame(arr, chunk_bytes):
    """The frame a fresh ZstdCompressor per chunk gives: what every earlier
    "zstd" frame holds, so reusing one compressor per thread must match."""
    views = codecs._chunk_views(np.ascontiguousarray(arr), chunk_bytes)
    payloads = [zstandard.ZstdCompressor(level=3).compress(bytes(v))
                for v in views]
    return codecs.assemble_frame("zstd", arr.dtype, arr.shape, arr.nbytes,
                                 chunk_bytes, payloads)


def test_zstd_frames_match_a_fresh_compressor(rng):
    arr = (rng.standard_normal(300_000) * 50).astype(np.int16)
    for chunk_bytes in (1 << 14, codecs.DEFAULT_CHUNK):
        blob, _ = codecs.encode(arr, "zstd", chunk_bytes=chunk_bytes)
        assert blob[5] == 6                     # codec id unchanged
        assert blob == _fresh_zstd_frame(arr, chunk_bytes)


def test_zstd_concurrent_encodes_match_serial(rng):
    """Many arrays coded at once on the codec pool's threads give the
    frames a serial encode gives, and decode back concurrently: each
    thread's own (de)compressor is never shared."""
    import sys
    arrays = [(rng.standard_normal(20_000 + 997 * i) * 10).astype(np.float32)
              for i in range(48)]
    serial = [codecs.encode(a, "zstd", chunk_bytes=1 << 13)[0]
              for a in arrays]
    pool = codecs.codec_pool()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        futs = [pool.submit(codecs.encode, a, "zstd", chunk_bytes=1 << 13)
                for a in arrays]
        concurrent = [f.result(timeout=60)[0] for f in futs]
        back = [f.result(timeout=60)
                for f in [pool.submit(codecs.decode, b) for b in concurrent]]
    finally:
        sys.setswitchinterval(old)
    assert concurrent == serial
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(a, b)
    chunked, _ = codecs.encode(arrays[-1], "zstd", chunk_bytes=1 << 13,
                               pool=pool)
    assert chunked == serial[-1]


def _bf16_weights(seed):
    import ml_dtypes
    r = np.random.default_rng(seed)
    w = (r.standard_normal((512, 1024), dtype=np.float32) * 0.02)
    return w.astype(ml_dtypes.bfloat16).view(np.uint16)


def _coefficients(seed, square):
    import jax.numpy as jnp
    from repro.kernels import ref
    r = np.random.default_rng(seed)
    g = r.standard_normal((512, 1024), dtype=np.float32) * 1e-3
    return np.asarray(ref.compress(jnp.asarray(g * g if square else g),
                                   1e-2).q)


@pytest.mark.parametrize("leaf", ["bf16_weights", "q_first_moment",
                                  "q_second_moment"])
def test_zstd_stores_no_more_than_zlib(leaf):
    """The checkpoint's leaf classes at a fixed seed: the zstd default
    writes no more bytes than zlib-6 did."""
    arr = {"bf16_weights": lambda: _bf16_weights(1234),
           "q_first_moment": lambda: _coefficients(1234, False),
           "q_second_moment": lambda: _coefficients(1234, True)}[leaf]()
    zstd_blob, _ = codecs.encode(arr, "zstd")
    zlib_blob, _ = codecs.encode(arr, "zlib")
    assert len(zstd_blob) <= len(zlib_blob)
    np.testing.assert_array_equal(codecs.decode(zstd_blob), arr)


@pytest.mark.parametrize("stream", [False, True])
def test_lossy_blob_is_header_and_both_frames(stream):
    """The lossy frame, joined in one pass, holds the bytes of header +
    coefficient frame + scale frame; the kept share is the share of
    nonzero coefficients."""
    import jax.numpy as jnp
    from repro.core import lossy
    from repro.kernels import ops
    r = np.random.default_rng(7)
    x = jnp.asarray(r.standard_normal(3 * codecs.DEFAULT_CHUNK // 2,
                                      dtype=np.float32) * 1e-3)
    blob, st = lossy.compress_tensor(x, 1e-2, lossless="zstd", stream=stream)
    c = ops.spectral_compress(x, 1e-2)
    q, scale = np.asarray(c.q), np.asarray(c.scale)
    q_blob, _ = codecs.encode(q, "zstd")
    s_blob, _ = codecs.encode(scale, "zstd")
    header = lossy._lossy_header(c.dtype, c.n_elements, tuple(c.shape),
                                 len(q_blob), len(s_blob))
    assert blob == header + q_blob + s_blob
    assert st.stored_bytes == len(blob)
    assert st.kept_fraction == float(np.mean(q != 0))
