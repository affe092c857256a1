"""The MoE training cell on the CPU: the registry entry against the
configuration file; the plain reference (``reference_moe.py``) against the
program at the registry's reduced sizes (seeded weights bit for bit;
logits, loss, gradient and routing in float32); a smoke run of the
generator and the control at those sizes; its three readers on a record
kept from a chip run."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import reference_moe

CELL = "moonlight-16b-a3b.train.hybrid-ckpt"
FIXTURE = Path(__file__).parent / "fixtures" / "moe_record.json"
SEED = 2**31 + 11
# Limits at this reduced size, by the cells' rule, from readings here (5
# seeds on the CPU): sound runs read at most loss 3.7e-4, grad 1.3e-2,
# update 3.8e-2; the float8 control at least 1.7e-3, 0.40, 0.66. The
# router bias (16 entries here, loads of a few tokens, so ties decide
# some moves): sound runs at most 0.125, a bias that never moves 0.94.
SMALL_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 0.06, "update_gap": 0.15,
                "bias_gap": 0.5}


@pytest.fixture(scope="module")
def gen():
    return harness.load_generator("train_loop_moe")


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def test_registry_entry_is_the_published_config(gen, cell):
    """Every catalog number of the file, the published depth, experts and
    vocabulary, and the flags' meanings, against the program's entry and
    its one-chip share."""
    from repro.configs import base
    from repro.launch import train
    whole, share, cfg = gen.program_config(cell.config)
    gen.check_program(cfg, whole, share, train.StepConfig(), cell.traffic)
    assert (whole.n_layers, whole.moe.n_experts, whole.vocab_size) == (
        27, 64, 163840)
    assert (share.n_layers, share.moe.held, share.vocab_size) == (5, 8, 20480)
    assert share.n_params() == 568_484_608
    bad = dict(cell.config, rope_theta=10000)
    with pytest.raises(harness.BenchError, match="rope_theta"):
        gen.check_program(bad, whole, share, train.StepConfig(), cell.traffic)
    # a program without the entry (the parent of this configuration) fails
    # before anything compiles
    gone = dict(cell.config, program={"registry": "moonshot-v1-16b-a3b"})
    with pytest.raises(harness.BenchError, match="no registry entry"):
        gen.program_config(gone)
    assert "moonshot-v1-16b-a3b" not in base.ARCH_IDS


@pytest.fixture(scope="module")
def small(gen, cell):
    from repro.models import params as P, transformer
    whole, share, cfg = gen.program_config(cell.config, smoke=True)
    prog = P.materialize(jax.random.PRNGKey(SEED),
                         transformer.param_spec(share))
    return share, cfg, prog


def test_seeded_weights_match_the_entry_point(small):
    _, cfg, prog = small
    ref = reference.flatten(reference_moe.init_params(cfg, SEED))
    got = reference.flatten(prog)
    assert ref.keys() == got.keys()
    for k in ref:
        assert ref[k].dtype == got[k].dtype, k
        np.testing.assert_array_equal(np.asarray(ref[k], np.float32),
                                      np.asarray(got[k], np.float32),
                                      err_msg=str(k))


def test_forward_loss_grads_and_routing_in_float32(small):
    from repro.models import transformer
    share, cfg, prog = small
    toks, labels = (jnp.asarray(x) for x in
                    reference.synth_batch(2, 32, cfg["vocab_size"], 3, 0))
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), prog)
    with jax.default_matmul_precision("highest"):
        lg, aux, _ = transformer.forward(p32, share, toks)
        rows = [reference_moe.forward(prog, cfg, t) for t in toks]
        (loss, stats), grads = jax.value_and_grad(
            lambda p: transformer.train_loss_and_stats(
                p, share, {"tokens": toks, "labels": labels}),
            has_aux=True)(p32)
        r_loss, r_grads, r_load, _ = reference_moe.loss_and_grads(
            prog, cfg, toks, labels)
    want = np.stack([r[0] for r in rows])
    np.testing.assert_allclose(np.asarray(lg[..., :cfg["vocab_size"]]), want,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  np.asarray(r_load))
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-5)
    got, ref = reference.flatten(grads), reference.flatten(r_grads)
    for k, g in ref.items():
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(g),
                                   rtol=2e-3, atol=1e-6, err_msg=str(k))
    assert not np.any(np.asarray(got[("moe_blocks", "moe", "e_bias")]))


def _small_cell():
    cell = harness.load_cell(CELL)
    cell.traffic.update(seq_len=64, batch=2, limits=SMALL_LIMITS)
    return cell


def test_smoke_run_is_correct(tmp_path):
    cell = _small_cell()
    out = harness.run(CELL, SEED, 1, False, time.perf_counter(), cell=cell,
                      smoke=True, require_chip=False, workdir=tmp_path / "run")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert out["checks"]["ckpt_missing"]["value"] == 0
    assert out["checks"]["ckpt_lossy_rel_l2"]["value"] > 0


@pytest.mark.parametrize("fault", ["never", "reversed"])
def test_smoke_run_with_a_wrong_bias_update_is_not_correct(tmp_path,
                                                          monkeypatch, fault):
    """The correction bias takes no gradient, so only ``bias_gap`` sees a
    program that does not move it, or moves it toward the loaded experts."""
    from repro.models import moe as moe_lib
    update = moe_lib.update_bias
    monkeypatch.setattr(moe_lib, "update_bias", {
        "never": lambda bias, load, rate: bias,
        "reversed": lambda bias, load, rate: update(bias, load, -rate)}[fault])
    cell = _small_cell()
    out = harness.run(CELL, SEED, 1, False, time.perf_counter(), cell=cell,
                      smoke=True, require_chip=False, workdir=tmp_path / "run")
    assert not out["correct"]
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed == ["bias_gap"], out["checks"]


def test_bias_gap_counts_entries_moved_otherwise(gen):
    gamma = 1e-3
    ref = np.array([[3, 1, -1, -3], [1, 1, -1, -1]], np.float32) * gamma
    assert gen.bias_gap(ref + 1e-7, ref, gamma) == 0.0
    # one step of one entry the other way: 2 gamma apart
    moved = ref.copy()
    moved[0, 1] -= 2 * gamma
    assert gen.bias_gap(moved, ref, gamma) == 1 / 8
    assert gen.bias_gap(np.zeros_like(ref), ref, gamma) == 1.0
    assert gen.bias_gap(-ref, ref, gamma) == 1.0


def test_control_is_not_correct():
    import control_moe
    out = control_moe.training_control(_small_cell(), SEED, smoke=True)
    assert not out["control_passes"], out
    assert out["flip_share"] > 0


def _fixture_record():
    import devtrace
    import work
    rec = json.loads(FIXTURE.read_text())
    cell = harness.load_cell(CELL)
    tr = rec.pop("trace")
    rec["trace"] = devtrace.TraceSummary(
        window_s=tr["window_s"], busy_s=tr["busy_s"], op_s=tr["op_s"],
        module_s=tr["module_s"], module_n=tr["module_n"],
        idle_s=tr["idle_s"])
    rec.update(config=cell.config, traffic=cell.traffic,
               peaks=work.peaks("TPU v5 lite"))
    return rec


@pytest.mark.parametrize("name", ["mfu.train.moe", "moe_experts_roofline",
                                  "compress_roofline_per_save"])
def test_readers_on_a_recorded_chip_run(name):
    rec = _fixture_record()
    mod = dict((e["name"], m) for e, m in harness.load_cell(CELL).per_layer)[name]
    value = mod.read(rec)
    assert value is not None and 0 < value < 100, value
    # a program that counts nothing and shows no such kernel: no reading
    bare = dict(rec, counters={}, saves_in_window=0)
    bare["trace"].op_s = {}
    bare["trace"].module_s, bare["trace"].module_n = {}, {}
    assert mod.read(bare) is None
