"""``correct`` on the CPU at the registry's reduced sizes: a sound run of
each training cell passes; a run with the timed step broken underneath, in
each way a one-chip training cell can be, fails; and the control (the
reference in float8 in the program's place) fails the same limits."""
import time

import pytest

import checks
import control
import harness

SEED = 2**31 + 101
# Limits at this reduced size, set by the cells' own rule from readings
# here (5 seeds on the CPU): sound runs read at most loss 3.3e-5, grad
# 3.5e-3, update 1.4e-2; the float8 control at least 2.0e-4, 1.2e-2, 3.1e-2.
SMALL_LIMITS = {"loss_gap": 9e-5, "grad_gap": 8e-3, "update_gap": 2.2e-2}


def _cell(name):
    cell = harness.load_cell(name)
    cell.traffic.update(seq_len=64, batch=2, limits=SMALL_LIMITS)
    return cell


def _run(cell, tmp_path, seed=SEED):
    return harness.run(cell.name, seed, 1, False, time.perf_counter(),
                       cell=cell, smoke=True, require_chip=False,
                       workdir=tmp_path / "run")


@pytest.mark.parametrize("name", ["smollm-135m.train.hybrid-ckpt",
                                  "smollm-135m.train.plain"])
def test_sound_run_is_correct(name, tmp_path):
    out = _run(_cell(name), tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {e["name"] for e in _cell(name).end_to_end}
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    if name.endswith("hybrid-ckpt"):
        assert out["checks"]["ckpt_missing"]["value"] == 0
        assert out["checks"]["ckpt_lossy_rel_l2"]["value"] > 0


def _break(kind):
    import jax.numpy as jnp

    def wrap(step):
        def broken(state, batch):
            if kind == "half_batch":
                half = batch["tokens"].shape[0] // 2
                batch = {k: jnp.concatenate([v[:half], v[:half]])
                         for k, v in batch.items()}
            new_state, metrics = step(state, batch)
            if kind == "state_unchanged":
                return state, metrics
            if kind == "loss_altered":
                metrics = dict(metrics, loss=metrics["loss"] * 1.01)
            return new_state, metrics
        return broken
    return wrap


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", "update_gap"),
    ("half_batch", "grad_gap"),
    ("loss_altered", "loss_gap"),
])
def test_broken_step_is_not_correct(fault, fails, tmp_path, monkeypatch):
    from repro.launch import train
    orig = train.jit_train_step

    def jit_broken(*a, **k):
        jitted, st_sh, b_sh, ef = orig(*a, **k)
        return _break(fault)(jitted), st_sh, b_sh, ef

    monkeypatch.setattr(train, "jit_train_step", jit_broken)
    out = _run(_cell("smollm-135m.train.plain"), tmp_path)
    assert not out["correct"]
    c = out["checks"][fails]
    assert c["value"] > c["limit"], out["checks"]


def test_control_is_not_correct():
    from repro.configs import base
    cell = _cell("smollm-135m.train.plain")
    cell.config = checks.hf_config_of(base.get("smollm-135m", smoke=True),
                                      cell.config)
    out = control.training_control(cell, SEED)
    assert not out["control_passes"], out
