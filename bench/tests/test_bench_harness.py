"""The harness is driven by data: a new configuration, traffic mix, metric
and cell are new files, found by name, with no code file edited. Off the
TPU, or without the program beside it, the command fails with no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads(name):
    cell = harness.load_cell(name, ROOT)
    assert cell.traffic["kind"]
    assert (ROOT / "bench" / "generators" / f"{cell.traffic['kind']}.py").exists()
    assert any(e["name"] == "setup_s" for e in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for entry, mod in cell.per_layer:
        assert callable(mod.read)
        assert entry["moves"] in {e["name"] for e in cell.end_to_end}


def _copy_bench(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def _digest(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*.py")}


def test_new_cell_is_new_files_only(tmp_path):
    root = _copy_bench(tmp_path)
    before = _digest(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/smollm-135m.json").read_text())
    (root / "bench/configs/smollm-135m-long.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/train-plain.json").read_text())
    tr["seq_len"] = 4096
    (root / "bench/traffic/train-plain-4k.json").write_text(json.dumps(tr))
    (root / "bench/metrics/data_wait_ms.train.py").write_text(
        'UNIT = "ms"\nLAYER = "data pipeline"\nMOVES = "train_tokens_per_s"\n'
        "def read(rec):\n    return 1.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="smollm-135m-long",
                                 file="bench/configs/smollm-135m-long.json"))
    bench["workloads"].append({"name": "smollm-135m-long.train.plain-4k",
                               "config": "smollm-135m-long",
                               "traffic": "train-plain-4k", "chips": 1,
                               "why": "longer rows"})
    bench["end_to_end"][0]["workloads"].append("smollm-135m-long.train.plain-4k")
    bench["per_layer"].append({"name": "data_wait_ms.train", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "data pipeline", "moves": "train_tokens_per_s",
                               "workloads": ["smollm-135m-long.train.plain-4k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("smollm-135m-long.train.plain-4k", root)
    assert cell.traffic["seq_len"] == 4096
    assert cell.config_name == "smollm-135m-long"
    assert [e["name"] for e, _ in cell.per_layer] == ["data_wait_ms.train"]
    assert cell.per_layer[0][1].read({}) == 1.0
    assert {e["name"] for e in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert harness.load_generator(cell.traffic["kind"], root).run
    after = _digest(root)
    assert all(after[p] == before[p] for p in before)


def test_reader_must_agree_with_benchmark_json(tmp_path):
    root = _copy_bench(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"][0]["unit"] = "fraction"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(harness.BenchError, match="UNIT"):
        harness.load_cell(CELLS[0], root)


def test_unknown_workload_and_kind(tmp_path):
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell("nope", ROOT)
    with pytest.raises(harness.BenchError, match="no generator"):
        harness.load_generator("carrier_pigeon", ROOT)


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cwd / ".jax_cache"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU found" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = _copy_bench(tmp_path)
    r = _run(root)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
