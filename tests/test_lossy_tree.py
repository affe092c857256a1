"""core/lossy: pytree compression, policies, framed-blob roundtrips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st   # optional-hypothesis shim

from repro.core import lossy


def _tree(rng):
    return {
        "w": jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32)),
        "opt": {"mu": jnp.asarray(rng.standard_normal(512)
                                  .astype(np.float32)),
                "nu": jnp.asarray(np.abs(rng.standard_normal(512))
                                  .astype(np.float32))},
    }


def test_policy_selects_moments_only(rng):
    tree = _tree(rng)
    blobs, stats = lossy.compress_tree(tree, eps=1e-2, lossless="zstd")
    lossy_keys = {k for k, b in blobs.items() if b[:4] == lossy.LOSSY_MAGIC}
    assert lossy_keys == {"['opt']['mu']", "['opt']['nu']"}


def test_restore_tree_structure_and_errors(rng):
    tree = _tree(rng)
    blobs, _ = lossy.compress_tree(tree, eps=1e-2, lossless="zstd")
    rt = lossy.restore_tree(tree, blobs)
    assert jax.tree_util.tree_structure(rt) == \
        jax.tree_util.tree_structure(tree)
    np.testing.assert_array_equal(np.asarray(rt["w"]), np.asarray(tree["w"]))
    rel = float(jnp.linalg.norm(rt["opt"]["mu"] - tree["opt"]["mu"])
                / jnp.linalg.norm(tree["opt"]["mu"]))
    assert rel <= lossy.error_bound(1e-2) + 1e-5


def test_frame_roundtrip_bf16():
    x = jnp.asarray(np.linspace(-2, 2, 777), dtype=jnp.bfloat16)
    blob, st_ = lossy.compress_tensor(x, eps=1e-2, lossless="zstd")
    y = lossy.decompress_tensor(blob)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape
    err = float(jnp.max(jnp.abs((y - x).astype(jnp.float32))))
    assert err < 0.1


def test_measure_flag_reports_error(rng):
    x = jnp.asarray(rng.standard_normal(2048).astype(np.float32))
    _, st_ = lossy.compress_tensor(x, eps=1e-1, lossless="zstd",
                                   measure=True)
    assert st_.rel_l2_error is not None
    assert st_.rel_l2_error <= lossy.error_bound(1e-1) + 1e-5


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999),
       eps=st.sampled_from([1e-1, 1e-2]),
       lossless=st.sampled_from(["zlib", "bz2"]))
def test_tensor_blob_property(seed, eps, lossless):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(rng.integers(1, 2000))
                    .astype(np.float32))
    blob, st_ = lossy.compress_tensor(x, eps=eps, lossless=lossless)
    y = lossy.decompress_tensor(blob)
    assert y.shape == x.shape
    num = float(jnp.linalg.norm(y - x))
    den = max(float(jnp.linalg.norm(x)), 1e-30)
    assert num / den <= lossy.error_bound(eps) + 1e-4
