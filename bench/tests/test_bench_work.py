"""The yardstick's arithmetic: peaks, model FLOPs, compress work, and the
per-layer readers' shares staying within 100 % on made-up readings."""
from pathlib import Path

import pytest

import devtrace
import harness
import work

METRICS = Path(__file__).resolve().parents[1] / "metrics"
SMOLLM = {"hidden_size": 576, "intermediate_size": 1536, "num_hidden_layers": 30,
          "num_attention_heads": 9, "num_key_value_heads": 3,
          "tie_word_embeddings": True, "vocab_size": 49152}


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk.bf16_flops == 197e12 and pk.hbm_bytes == 819e9
    assert "v5e" in pk.source
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("cpu")


def test_param_count_matches_the_program():
    from repro.configs import base
    assert work.n_params(SMOLLM) == base.get("smollm-135m").n_params()


def test_model_flops_smollm_step():
    f = work.model_flops(SMOLLM, kind="train", seq_len=2048, batch=8)
    tokens = 8 * 2048
    attn = 12 * 30 * 9 * 64 * 2048 * 0.5 * tokens
    assert f == pytest.approx(6 * work.n_params(SMOLLM) * tokens + attn)
    assert 1.6e13 < f < 1.7e13


def test_compress_work_from_leaf_shapes_only():
    from repro.kernels import ops
    # a leaf of 1000 elements is 4 blocks; the program pads it to a bucket
    # of 8 rows, which the work must not see
    flops, nbytes = work.compress_work([1000])
    assert flops == 2 * 256 * 4 * 256
    assert nbytes == 4 * 1000 + 1000 + 4 * 4
    assert ops._bucket_rows(4) > 4
    assert work.compress_work([1000, 3000]) == tuple(
        a + b for a, b in zip(work.compress_work([1000]), work.compress_work([3000])))


def test_ideal_seconds_takes_the_larger_bound():
    pk = work.peaks("TPU v5 lite")
    assert work.ideal_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert work.ideal_seconds(0, 819e9, pk) == pytest.approx(1.0)


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py", f"t_{name.replace('.', '_')}")


def _record(busy=0.5, window=1.0, module_s=0.01, launches=1):
    tr = devtrace.TraceSummary(window_s=window, busy_s=busy,
                               module_s={"jit__compress_tree_packed": module_s},
                               module_n={"jit__compress_tree_packed": launches})
    traffic = {"seq_len": 2048, "batch": 8}
    return {"trace": tr, "config": SMOLLM, "traffic": traffic,
            "peaks": work.peaks("TPU v5 lite"), "window": (0.0, window),
            "window_s": window, "steps_in_window": 1,
            "compress_leaf_sizes": [134_515_008] * 2,
            "spans": [("insitu-device/checkpoint", 0.1, 0.2, "MainThread", 10),
                      ("insitu-async/checkpoint", 0.2, 0.9, "w", 10)],
            "main_thread": "MainThread"}


def test_shares_within_100_percent():
    rec = _record()
    assert 0 < _reader("idle_share.train").read(rec) <= 100
    assert 0 < _reader("compress_roofline").read(rec) <= 100
    # a step at the chip's peak reads 100 % model FLOP utilization
    f = work.model_flops(SMOLLM, kind="train", seq_len=2048, batch=8)
    rec["window_s"] = f / 197e12
    assert _reader("mfu.train").read(rec) == pytest.approx(100.0)


def test_readers_find_nothing_without_a_trace():
    rec = _record()
    rec["trace"] = None
    assert _reader("idle_share.train").read(rec) is None
    assert _reader("compress_roofline").read(rec) is None
    rec = _record(launches=0, module_s=0.0)
    assert _reader("compress_roofline").read(rec) is None


def test_span_readers():
    rec = _record()
    assert _reader("insitu_block_ms_per_step.train").read(rec) == pytest.approx(100.0)
    assert _reader("ckpt_host_ms_per_save").read(rec) == pytest.approx(700.0)
    rec["spans"] = []
    assert _reader("ckpt_host_ms_per_save").read(rec) is None
