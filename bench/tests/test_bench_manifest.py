"""BENCHMARK.json and the files it names: the contract's shape rules, and
that a cell, configuration, traffic mix or metric added as files only is
found by the harness."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT


@pytest.fixture()
def man():
    return harness.manifest()


def test_manifest_is_valid(man):
    assert harness.validate(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}


@pytest.mark.parametrize("section,key,value,problem", [
    ("end_to_end", "name", "bad name", "bad name"),
    ("end_to_end", "unit", "tokens per s", "bad unit"),
    ("per_layer", "name", "x/y", "bad name"),
    ("per_layer", "unit", "µs", "bad unit"),
    ("per_layer", "better", "up", "bad better"),
])
def test_names_and_units_are_checked(man, section, key, value, problem):
    bad = copy.deepcopy(man)
    bad[section][0][key] = value
    assert any(problem in p for p in harness.validate(bad))


def test_every_layer_metric_reports_its_moves(man):
    for w in man["workloads"]:
        e2e = {m["name"] for m in harness.end_to_end_for(man, w["name"])}
        layer = harness.per_layer_for(man, w["name"])
        assert layer and "setup_s" in e2e
        assert all(m["moves"] in e2e for m in layer)
    bad = copy.deepcopy(man)
    cell = bad["workloads"][0]["name"]
    bad["end_to_end"].append({"name": "other_s", "unit": "s",
                              "better": "lower", "bound": 0.1,
                              "source": "host_clock", "workloads": []})
    bad["per_layer"].append({"name": "orphan_ms", "unit": "ms",
                             "better": "lower", "source": "device_trace",
                             "layer": "device", "moves": "other_s",
                             "workloads": [cell]})
    problems = harness.validate(bad)
    assert any("but not its moves" in p for p in problems)


def test_at_most_half_the_cells_on_four_chips(man):
    bad = copy.deepcopy(man)
    for w in bad["workloads"]:
        w["chips"] = 4
    assert any("ask for 4 chips" in p for p in harness.validate(bad))


def _copy_bench(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def test_files_only_additions_are_found(tmp_path, man):
    root = _copy_bench(tmp_path)
    bench = str(root / "bench")
    (root / "bench/configs/tiny-lm.json").write_text(json.dumps(
        {"source": "test", "n_layer": 2, "n_embd": 64, "n_head": 2,
         "n_inner": 128, "vocab_size": 256, "n_positions": 64,
         "layer_norm_epsilon": 1e-6}))
    (root / "bench/mixes/markov2-b2-s16.json").write_text(json.dumps(
        {"kind": "markov2", "batch": 2, "seq": 16, "pool": 2}))
    cell = dict(harness.cell_file(man["workloads"][0]["name"]),
                config="tiny-lm", traffic="markov2-b2-s16")
    (root / "bench/workloads/tiny-lm-train.json").write_text(json.dumps(cell))
    (root / "bench/metrics/steps_seen.train.py").write_text(
        "def read(ctx):\n    return ctx.layer.get('steps')\n")
    new = json.loads((root / "BENCHMARK.json").read_text())
    new["configs"].append({"name": "tiny-lm", "source": "test",
                           "file": "bench/configs/tiny-lm.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "tiny-lm-train", "config": "tiny-lm",
                             "traffic": "markov2-b2-s16", "chips": 1,
                             "why": "test"})
    for m in new["end_to_end"]:
        if m["name"] == "train_tokens_per_s" and "workloads" in m:
            m["workloads"].append("tiny-lm-train")
    new["per_layer"].append({"name": "steps_seen.train", "unit": "1",
                             "better": "higher", "source": "program_span",
                             "layer": "train step", "moves":
                             "train_tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    man2 = harness.manifest(str(root))
    assert harness.validate(man2, str(root)) == []
    names = [m["name"] for m in harness.per_layer_for(man2, "tiny-lm-train")]
    assert "steps_seen.train" in names and "train_mfu" in names
    assert harness.cell_file("tiny-lm-train", bench)["config"] == "tiny-lm"
    assert harness.mix_file("markov2-b2-s16", bench)["seq"] == 16
    assert harness.config_file("tiny-lm", bench)["n_embd"] == 64
    read = harness.metric_reader("steps_seen.train", bench)

    class Ctx:
        layer = {"steps": 5}
    assert read(Ctx) == 5


def _run(cwd, strip_path=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if strip_path:
        env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         harness.manifest()["workloads"][0]["name"], "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_chip_exits_nonzero_without_a_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def test_only_the_benchmark_files_exit_nonzero(tmp_path):
    root = _copy_bench(tmp_path)
    r = _run(str(root), strip_path=True)
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
