"""Spectral lossy codec API: error-bounded pytree compression.

Wraps the kernels (Pallas on TPU, interpret/jnp on CPU) with:
  * pytree walking (compress a whole checkpoint state in one call)
  * the lossy -> lossless two-stage pipeline of the paper's hybrid mode
    (device kernel produces dense int8 q + per-block scales; the host lossless
    codec then removes the zero runs — exactly NEKO's lossy-on-GPU +
    Bzip2-on-host split)
  * error-bound accounting: relative-L2 <= eps (threshold) + sqrt(B)/254
    (int8 quantization); tests enforce the combined bound.

A policy decides which leaves may be lossy: by default only optimizer
*moments* (noise-dominated statistics — the ML analog of the paper's
"keep the energetic motions" physics argument) are lossy; weights stay
lossless. Override per-call.
"""
from __future__ import annotations

import io
import struct
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codecs
from repro.kernels import ops, ref

PyTree = Any

LOSSY_MAGIC = b"RPLY"


@dataclass(frozen=True)
class LossyStats:
    raw_bytes: int
    stored_bytes: int
    kept_fraction: float
    rel_l2_error: Optional[float] = None   # only when measure=True

    @property
    def ratio(self) -> float:
        if self.raw_bytes == 0:
            return 0.0
        return (self.raw_bytes - self.stored_bytes) / self.raw_bytes


def error_bound(eps: float) -> float:
    return ref.error_bound(eps)


# ---------------------------------------------------------------------------
# single tensor: device lossy stage -> host lossless stage -> framed bytes
# ---------------------------------------------------------------------------

def _lossy_header(dtype, n_elements: int, shape: tuple,
                  qlen: int, slen: int) -> bytes:
    dt = jnp.dtype(dtype).name.encode()   # name token: handles bf16
    return LOSSY_MAGIC + struct.pack("<B", len(dt)) + dt + struct.pack(
        "<qB", n_elements, len(shape)) + struct.pack(
        f"<{len(shape)}q", *shape) + struct.pack("<qq", qlen, slen)


def _raw_bytes(dtype, shape: tuple) -> int:
    return (int(np.prod(shape)) if shape else 1) \
        * np.dtype(jnp.dtype(dtype)).itemsize


def frame_compressed(c: ref.Compressed, lossless: str,
                     pool=None) -> tuple[bytes, LossyStats]:
    """Host lossless stage: pack a device-produced Compressed into bytes.

    ``pool`` fans the lossless chunks of a large coefficient buffer out
    across the shared codec executor (see ``codecs.codec_pool``).
    """
    q = np.asarray(c.q)
    scale = np.asarray(c.scale)
    q_blob, _ = codecs.encode(q, lossless, pool=pool)
    s_blob, _ = codecs.encode(scale, lossless, pool=pool)
    shape = tuple(int(d) for d in c.shape)
    header = _lossy_header(c.dtype, c.n_elements, shape,
                           len(q_blob), len(s_blob))
    blob = b"".join((header, q_blob, s_blob))
    return blob, LossyStats(_raw_bytes(c.dtype, shape), len(blob),
                            np.count_nonzero(q) / max(q.size, 1))


def _frame_chunked_q(chunks, lossless: str, pool=None) -> tuple[bytes, float]:
    """Streamed host lossless stage for device-chunked int8 coefficients.

    Every chunk's D2H copy is started up front, then each chunk is
    losslessly compressed as soon as it lands on the host — the framing
    never synchronises on one monolithic coefficient buffer. The frame is
    byte-identical to ``codecs.encode(concat(chunks))`` because the device
    chunks are cut at the codec's own chunk boundary.

    Returns ``(frame bytes, kept fraction)``.
    """
    _, comp, _ = codecs.compressor(lossless)
    for ch in chunks:
        if hasattr(ch, "copy_to_host_async"):
            ch.copy_to_host_async()
    use_pool = pool is not None and len(chunks) > 1
    nonzero = total = 0
    pending = []
    for ch in chunks:
        a = np.asarray(ch)            # waits for *this* chunk only
        nonzero += int(np.count_nonzero(a))
        total += a.size
        view = codecs._byte_view(a)
        pending.append(pool.submit(comp, view) if use_pool else comp(view))
    payloads = [p.result() for p in pending] if use_pool else pending
    n_blocks = sum(int(ch.shape[0]) for ch in chunks)
    blob = codecs.assemble_frame(lossless, np.int8, (n_blocks, ref.BLOCK),
                                 n_blocks * ref.BLOCK, codecs.DEFAULT_CHUNK,
                                 payloads)
    return blob, nonzero / max(total, 1)


def compress_tensor(x: jax.Array | np.ndarray, eps: float = 1e-2, *,
                    lossless: str,
                    measure: bool = False, pool=None,
                    stream: bool | None = None) -> tuple[bytes, LossyStats]:
    """Device lossy stage + host lossless stage for one tensor.

    ``stream`` (default: auto — multi-chunk payloads without ``measure``)
    uses the fused quantize+chunking kernel path: the int8 coefficients
    leave the device pre-split at codec chunk boundaries and are framed
    chunk-by-chunk, overlapping D2H with lossless packing. Output bytes are
    identical either way.
    """
    x = jnp.asarray(x)
    if stream is None:
        stream = not measure and x.size > codecs.DEFAULT_CHUNK
    if stream and not measure:
        chunks, scale, n = ops.spectral_compress_chunked(
            x, eps, chunk_blocks=codecs.DEFAULT_CHUNK // ref.BLOCK)
        q_blob, kept = _frame_chunked_q(chunks, lossless, pool)
        s_blob, _ = codecs.encode(np.asarray(scale), lossless, pool=pool)
        shape = tuple(int(d) for d in x.shape)
        header = _lossy_header(x.dtype, n, shape, len(q_blob), len(s_blob))
        blob = b"".join((header, q_blob, s_blob))
        return blob, LossyStats(_raw_bytes(x.dtype, shape), len(blob),
                                float(kept))
    c = ops.spectral_compress(x, eps)                # device lossy stage
    blob, st = frame_compressed(c, lossless, pool)   # host lossless stage
    if measure:
        st = LossyStats(st.raw_bytes, st.stored_bytes, st.kept_fraction,
                        ref.rel_l2_error(x, ops.spectral_decompress(c)))
    return blob, st


def decompress_tensor(blob: bytes, pool=None) -> jax.Array:
    if blob[:4] != LOSSY_MAGIC:
        raise ValueError("bad lossy frame magic")
    off = 4
    (dtlen,) = struct.unpack_from("<B", blob, off)
    off += 1
    name = blob[off:off + dtlen].decode()
    try:
        dtype = np.dtype(name)
    except TypeError:
        import ml_dtypes
        dtype = np.dtype(getattr(ml_dtypes, name))
    off += dtlen
    n_elements, ndim = struct.unpack_from("<qB", blob, off)
    off += 9
    shape = struct.unpack_from(f"<{ndim}q", blob, off)
    off += 8 * ndim
    qlen, slen = struct.unpack_from("<qq", blob, off)
    off += 16
    q = jnp.asarray(codecs.decode(blob[off:off + qlen], pool=pool))
    scale = jnp.asarray(codecs.decode(blob[off + qlen:off + qlen + slen],
                                      pool=pool))
    c = ref.Compressed(q, scale, n_elements, tuple(shape), jnp.dtype(dtype))
    return ops.spectral_decompress(c)


# ---------------------------------------------------------------------------
# pytree policy + walking
# ---------------------------------------------------------------------------

def moments_only_policy(path: tuple, leaf) -> bool:
    """Default: lossy for optimizer moment statistics, lossless for weights."""
    keys = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
    return any(tok in keys for tok in ("mu", "nu", "m1", "m2", "moment"))


def compress_tree(tree: PyTree, eps: float = 1e-2, *, lossless: str,
                  policy: Callable[[tuple, Any], bool] = moments_only_policy,
                  ) -> tuple[dict[str, bytes], dict[str, LossyStats | codecs.CompressionStats]]:
    """Returns ({path: framed blob}, {path: stats}). Lossless leaves use codecs."""
    blobs: dict[str, bytes] = {}
    stats: dict[str, Any] = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        if policy(path, leaf):
            blob, st = compress_tensor(leaf, eps, lossless=lossless)
        else:
            blob, st = codecs.encode(arr, lossless)
        blobs[key] = blob
        stats[key] = st
    return blobs, stats


def decompress_blob(blob: bytes, pool=None) -> np.ndarray | jax.Array:
    if blob[:4] == LOSSY_MAGIC:
        return decompress_tensor(blob, pool)
    return codecs.decode(blob, pool=pool)


class SpectralLossyCodec:
    """Registry adapter: the device lossy stage + host lossless stage as one
    ``repro.core.compression`` Codec. Roundtrip error is relative-L2 bounded
    by ``error_bound()`` (threshold + int8 quantization terms)."""

    lossy = True

    def __init__(self, name: str = "spectral", eps: float = 1e-2,
                 lossless: str = "zlib") -> None:
        self.name = name
        self.eps = eps
        self.lossless = lossless

    def encode(self, arr) -> bytes:
        return compress_tensor(arr, self.eps, lossless=self.lossless)[0]

    def decode(self, blob: bytes) -> np.ndarray:
        return np.asarray(decompress_tensor(blob))

    def error_bound(self) -> float:
        return error_bound(self.eps)


from repro.core import compression as _compression  # noqa: E402

_compression.register(SpectralLossyCodec())
_compression.register(SpectralLossyCodec("spectral-coarse", eps=1e-1))


def restore_tree(template: PyTree, blobs: dict[str, bytes]) -> PyTree:
    """Rebuild a pytree (same structure as template) from framed blobs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        arr = decompress_blob(blobs[key])
        arr = jnp.asarray(arr)
        if hasattr(leaf, "dtype"):
            arr = arr.astype(leaf.dtype).reshape(leaf.shape)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
