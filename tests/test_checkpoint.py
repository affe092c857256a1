"""Checkpointing: 3 in-situ modes, atomicity, retention, elastic restore."""
import json
import os
import shutil
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                              serialization as ser)
from repro.core import codecs, lossy
from repro.core.insitu import InSituMode
from repro.kernels import ref


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (128, 64), jnp.float32)
              .astype(jnp.bfloat16),
              "b": jnp.zeros((64,), jnp.float32)}
    st = optim.init(params, optim.AdamWConfig())
    st = st._replace(mu=jax.tree.map(
        lambda x: x + 0.125, st.mu))
    return {"params": params, "opt": {"mu": st.mu, "nu": st.nu},
            "step": jnp.asarray(3, jnp.int32)}


@pytest.mark.parametrize("mode", list(InSituMode))
def test_roundtrip_all_modes(mode, tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), mode=mode,
                                             every=1, keep=5))
    mgr.save(10, state)
    mgr.wait_idle()
    mgr.finish()
    step, restored = mgr.restore(state)
    assert step == 10
    # weights are bit-exact (lossless path)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"].astype(jnp.float32)),
        np.asarray(state["params"]["w"].astype(jnp.float32)))
    # moments are lossy but error-bounded
    err = float(jnp.max(jnp.abs(
        restored["opt"]["mu"]["w"].astype(jnp.float32)
        - state["opt"]["mu"]["w"].astype(jnp.float32))))
    assert err < 0.05
    assert int(restored["step"]) == 3


def _random_moments_state(seed=0):
    """``_state`` with moments drawn at random, so the lossy bound bites."""
    state = _state(seed)
    k_mu, k_nu = jax.random.split(jax.random.PRNGKey(seed + 1))

    def draw(k, tree, square):
        leaves, treedef = jax.tree.flatten(tree)
        ks = jax.random.split(k, len(leaves))
        out = []
        for kk, x in zip(ks, leaves):
            r = jax.random.normal(kk, x.shape, jnp.float32) * 1e-3
            out.append((r * r if square else r).astype(x.dtype))
        return jax.tree.unflatten(treedef, out)

    state["opt"] = {"mu": draw(k_mu, state["opt"]["mu"], False),
                    "nu": draw(k_nu, state["opt"]["nu"], True)}
    return state


def _stored_blobs(step_dir):
    """{leaf key: (stored blob, manifest entry)} of a v2 checkpoint."""
    manifest = json.loads((step_dir / "manifest.json").read_text())
    shards = {}
    out = {}
    for key, ent in manifest["leaves"].items():
        if ent["file"] not in shards:
            shards[ent["file"]] = (step_dir / ent["file"]).read_bytes()
        data = shards[ent["file"]]
        out[key] = (data[ent["offset"]:ent["offset"] + ent["bytes"]], ent)
    return out


def _frame_codec_ids(blob):
    """Codec id of every lossless frame in a stored leaf blob: one for a
    lossless leaf, two (coefficients, scales) inside a lossy leaf."""
    if blob[:4] == lossy.LOSSY_MAGIC:
        off = 5 + blob[4]
        _, ndim = struct.unpack_from("<qB", blob, off)
        off += 9 + 8 * ndim
        qlen, _ = struct.unpack_from("<qq", blob, off)
        off += 16
        return [blob[off + 5], blob[off + qlen + 5]]
    assert blob[:4] == codecs.MAGIC
    return [blob[5]]


def _max_rel_l2(restored, state):
    """Worst relative-L2 gap over the moment leaves."""
    worst = 0.0
    for part in ("mu", "nu"):
        for a, b in zip(jax.tree.leaves(restored["opt"][part]),
                        jax.tree.leaves(state["opt"][part])):
            worst = max(worst, ref.rel_l2_error(b.astype(jnp.float32),
                                                a.astype(jnp.float32)))
    return worst


# the moments restore into bf16: its rounding (unit roundoff 2**-9 of the
# decoded value) adds at most 2**-9 * (1 + bound) <= 2**-8 to the codec's bound
_BF16_RESTORE = 2.0 ** -8


@pytest.mark.parametrize("mode", list(InSituMode))
def test_default_codec_is_zstd_in_every_mode(mode, tmp_path):
    """The default config codes every stored frame with zstd (codec id 6);
    lossless leaves restore bit-exact, lossy ones within the stated bound."""
    state = _random_moments_state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), mode=mode,
                                             every=1))
    assert mgr.cfg.lossless == "zstd"
    mgr.save(5, state)
    mgr.wait_idle()
    mgr.finish()
    blobs = _stored_blobs(tmp_path / "step_000000005")
    assert sum(ent["lossy"] for _, ent in blobs.values()) == 4
    for key, (blob, _) in blobs.items():
        assert set(_frame_codec_ids(blob)) == {6}, key
    _, restored = mgr.restore(state)
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(restored["step"]) == 3
    assert _max_rel_l2(restored, state) <= (ref.error_bound(1e-2)
                                            + _BF16_RESTORE)


def test_zlib_checkpoint_restores_under_zstd_default(tmp_path):
    """A v2 checkpoint written with zlib (codec id 1) restores unchanged
    through a manager on the zstd default: restore reads each frame's id."""
    state = _random_moments_state(3)
    old = CheckpointManager(CheckpointConfig(str(tmp_path), lossless="zlib",
                                             mode=InSituMode.SYNC, every=1))
    old.save(9, state)
    _, want = old.restore(state)
    old.finish()
    blobs = _stored_blobs(tmp_path / "step_000000009")
    assert all(set(_frame_codec_ids(b)) == {1} for b, _ in blobs.values())

    new = CheckpointManager(CheckpointConfig(str(tmp_path), every=1))
    step, got = new.restore(state)
    new.finish()
    assert step == 9
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_default_codec_missing_is_a_clear_error(tmp_path, monkeypatch):
    """Without zstandard the default must refuse, not write zlib silently."""
    monkeypatch.delitem(codecs._COMPRESSORS, "zstd")
    with pytest.raises(ValueError, match="zstandard"):
        CheckpointConfig(str(tmp_path))
    assert CheckpointConfig(str(tmp_path), lossless="zlib").lossless == "zlib"


def test_v2_layout_single_shard_and_offset_table(tmp_path):
    """Default format: every blob packed into shard files named by an
    offset-table manifest — file count independent of leaf count."""
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1))
    mgr.save(4, state)
    d = tmp_path / "step_000000004"
    files = sorted(os.listdir(d))
    assert files == ["manifest.json", "shard_000.bin"]
    manifest = json.loads((d / "manifest.json").read_text())
    assert manifest["format"] == 2
    entries = manifest["leaves"]
    assert len(entries) == 7          # w, b, mu.{w,b}, nu.{w,b}, step
    for ent in entries.values():
        assert set(ent) == {"file", "offset", "bytes", "raw_bytes",
                            "lossy", "bf16"}
        assert ent["file"] == "shard_000.bin"
    # offsets tile the shard exactly: sorted offsets are contiguous
    spans = sorted((e["offset"], e["bytes"]) for e in entries.values())
    pos = 0
    for off, nbytes in spans:
        assert off == pos
        pos += nbytes
    assert pos == (d / "shard_000.bin").stat().st_size


def test_v2_multi_shard_roundtrip(tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1,
                                             shard_count=3))
    mgr.save(6, state)
    d = tmp_path / "step_000000006"
    shards = [f for f in os.listdir(d) if f.startswith("shard_")]
    assert 1 < len(shards) <= 3       # byte-balanced upper bound
    step, restored = mgr.restore(state)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"].astype(jnp.float32)),
        np.asarray(state["params"]["w"].astype(jnp.float32)))


def test_v1_format_still_writable_and_restores(tmp_path):
    """format=1 keeps the per-leaf-file layout (benchmark baseline)."""
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1,
                                             format=1, leaf_parallel=False))
    mgr.save(8, state)
    d = tmp_path / "step_000000008"
    manifest = json.loads((d / "manifest.json").read_text())
    assert manifest["format"] == 1
    blobs = [f for f in os.listdir(d) if f.endswith(".bin")]
    assert len(blobs) == len(manifest["leaves"])    # one file per leaf
    assert all("offset" not in e for e in manifest["leaves"].values())
    step, restored = mgr.restore(state)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"].astype(jnp.float32)),
        np.asarray(state["params"]["w"].astype(jnp.float32)))


def test_serial_encode_matches_leaf_parallel(tmp_path):
    """leaf_parallel only changes scheduling: stored bytes are identical."""
    state = _state()
    outs = {}
    for name, flag in (("fan", True), ("serial", False)):
        d = tmp_path / name
        mgr = CheckpointManager(CheckpointConfig(str(d), mode=InSituMode.SYNC,
                                                 every=1, leaf_parallel=flag))
        mgr.save(1, state)
        outs[name] = (d / "step_000000001" / "shard_000.bin").read_bytes()
    assert outs["fan"] == outs["serial"]


def test_config_validation_rejects_bad_values(tmp_path):
    with pytest.raises(ValueError, match="every"):
        CheckpointConfig(str(tmp_path), every=0)     # was: ZeroDivisionError
    with pytest.raises(ValueError, match="keep"):
        CheckpointConfig(str(tmp_path), keep=-1)
    with pytest.raises(ValueError, match="format"):
        CheckpointConfig(str(tmp_path), format=3)
    with pytest.raises(ValueError, match="shard_count"):
        CheckpointConfig(str(tmp_path), shard_count=0)


def test_checkpoint_compression_beats_raw(tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1))
    mgr.save(1, state)
    rep = mgr.reports[-1]
    assert rep.stored_bytes < rep.raw_bytes
    assert rep.lossy_leaves == 4  # mu.w mu.b nu.w nu.b


def test_retention(tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC,
                                             every=1, keep=2))
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert mgr.list_steps() == [3, 4]


def test_atomicity_partial_checkpoint_invisible(tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1))
    mgr.save(5, state)
    # simulate a crash mid-save: blobs written, no manifest
    broken = tmp_path / "step_000000009"
    os.makedirs(broken)
    (broken / "deadbeef.bin").write_bytes(b"partial")
    assert mgr.list_steps() == [5]          # 9 is invisible
    step, _ = mgr.restore(state)
    assert step == 5


def test_manifest_metadata_and_restart_counter(tmp_path):
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1))
    mgr.save(7, state, meta={"mesh": [1, 1], "arch": "smollm-135m"})
    d = tmp_path / "step_000000007"
    manifest = json.loads((d / "manifest.json").read_text())
    assert manifest["meta"]["arch"] == "smollm-135m"
    assert manifest["step"] == 7


def test_elastic_restore_resharding(tmp_path):
    """Restore under a different (1-device) mesh sharding — re-placement."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             mode=InSituMode.SYNC, every=1))
    mgr.save(2, state)
    mesh = make_mesh((1, 1), ("data", "model"))
    shard = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    step, restored = mgr.restore(state, shardings=shard)
    w = restored["params"]["w"]
    assert w.sharding == NamedSharding(mesh, P())


def _v1_lossless_frame(arr, codec_name="zlib"):
    """Byte-for-byte pre-chunking (v1) lossless frame."""
    import struct
    import zlib
    comp = {"zlib": lambda b: zlib.compress(b, 6)}[codec_name]
    arr = np.ascontiguousarray(arr)
    raw = arr.tobytes()
    dt = np.dtype(arr.dtype).str.encode()
    return (b"RPRC" + struct.pack("<BBB", 1, 1, len(dt)) + dt
            + struct.pack("<B", arr.ndim)
            + struct.pack(f"<{arr.ndim}q", *arr.shape)
            + struct.pack("<q", len(raw)) + comp(raw))


def test_v1_per_leaf_checkpoint_still_restores(tmp_path):
    """Backward compat: a checkpoint whose blobs are legacy v1 single-stream
    frames (the pre-chunking, per-leaf encoding) restores bit-exactly."""
    import struct

    from repro.core import lossy
    from repro.kernels import ops

    state = _state()
    host = ser.state_to_host(state)
    bf16_keys = {
        k for (p, l) in jax.tree_util.tree_flatten_with_path(state)[0]
        if l is not None and getattr(l, "dtype", None) == jnp.bfloat16
        for k in [jax.tree_util.keystr(p)]}
    encoded = {}
    for key, arr in host.items():
        if ".mu" in key or ".nu" in key or "'mu'" in key or "'nu'" in key:
            # per-leaf lossy frame with v1 *inner* lossless frames; bf16
            # leaves arrive as u16 bit-patterns and go via f32 (same as
            # encode_blobs)
            a = arr
            if key in bf16_keys:
                a = np.asarray(jnp.asarray(arr.view(np.uint16))
                               .view(jnp.bfloat16).astype(jnp.float32))
            c = ops.spectral_compress(jnp.asarray(a, jnp.float32), 1e-2)
            q_blob = _v1_lossless_frame(np.asarray(c.q))
            s_blob = _v1_lossless_frame(np.asarray(c.scale))
            shape = tuple(int(d) for d in c.shape)
            dt = jnp.dtype(c.dtype).name.encode()
            blob = (lossy.LOSSY_MAGIC + struct.pack("<B", len(dt)) + dt
                    + struct.pack("<qB", c.n_elements, len(shape))
                    + struct.pack(f"<{len(shape)}q", *shape)
                    + struct.pack("<qq", len(q_blob), len(s_blob))
                    + q_blob + s_blob)
            ent = {"bytes": len(blob), "lossy": True,
                   "raw_bytes": int(arr.nbytes), "bf16": False}
        else:
            blob = _v1_lossless_frame(arr)
            ent = {"bytes": len(blob), "lossy": False,
                   "raw_bytes": int(arr.nbytes),
                   "bf16": key in bf16_keys}
        encoded[key] = (blob, ent)
    d = str(tmp_path / "step_000000011")
    entries = ser.write_encoded(d, encoded)
    ser.write_manifest(d, 11, entries, {})

    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), every=1))
    step, restored = mgr.restore(state)
    assert step == 11
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"].astype(jnp.float32)),
        np.asarray(state["params"]["w"].astype(jnp.float32)))
    err = float(jnp.max(jnp.abs(
        restored["opt"]["mu"]["w"].astype(jnp.float32)
        - state["opt"]["mu"]["w"].astype(jnp.float32))))
    assert err < 0.05
    assert int(restored["step"]) == 3
    mgr.finish()


def test_resume_after_simulated_failure(tmp_path):
    """New manager over the same dir (a 'restarted job') sees the state."""
    state = _state()
    m1 = CheckpointManager(CheckpointConfig(str(tmp_path),
                                            mode=InSituMode.ASYNC, every=1))
    m1.save(42, state)
    m1.wait_idle()
    m1.finish()
    del m1   # job dies
    m2 = CheckpointManager(CheckpointConfig(str(tmp_path),
                                            mode=InSituMode.ASYNC, every=1))
    assert m2.latest_step() == 42
    step, restored = m2.restore(state)
    assert step == 42
    m2.finish()
