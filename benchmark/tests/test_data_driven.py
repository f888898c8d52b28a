"""A new configuration, cell, kind and layer metric are new files plus
``BENCHMARK.json`` entries: nothing that is there is edited."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def test_new_cell_kind_config_and_layer_metric_as_files_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    bdir = tmp_path / "benchmark"

    cfg = json.loads((bdir / "configs" / "gpt3-large-760m.json").read_text())
    cfg["name"] = "dummy-config"
    (bdir / "configs" / "dummy-config.json").write_text(json.dumps(cfg))
    cell = json.loads(
        (bdir / "workloads" / "gpt3-760m.pretrain.json").read_text())
    cell.update(name="dummy.cell", config="dummy-config", kind="train_twin")
    (bdir / "workloads" / "dummy.cell.json").write_text(json.dumps(cell))
    (bdir / "kinds" / "train_twin.py").write_text(
        "from benchmark.kinds.train import run  # a new kind, as a file\n")
    (bdir / "layer_metrics" / "dummy_steps.py").write_text(
        "def read(name, obs, cell, cfg, peak):\n"
        "    return float(obs['steps'])\n")

    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    bench["configs"].append({
        "name": "dummy-config", "source": "test",
        "file": "benchmark/configs/dummy-config.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy-config", "traffic": "cell",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("dummy.cell")
    bench["per_layer"].append({
        "name": "dummy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness",
        "moves": "train_tokens_per_s", "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(bdir / "run.py"), "--workload", "dummy.cell",
         "--seed", "4", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["metrics"]["dummy_steps"]["value"] >= 1
    # metrics of other cells are not read here; nothing there was edited
    assert set(line["metrics"]) == {"dummy_steps"}
    after = {p: p.read_bytes() for p in before}
    assert after == before

    # a name that cannot be found fails loudly
    cell["kind"] = "no_such_kind"
    (bdir / "workloads" / "dummy.cell.json").write_text(json.dumps(cell))
    out = subprocess.run(
        [sys.executable, str(bdir / "run.py"), "--workload", "dummy.cell",
         "--seed", "4", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and "no_such_kind" in out.stderr
    assert not out.stdout.strip()


def test_no_accelerator_no_result(tmp_path):
    """Without a TPU the measuring path exits non-zero and prints nothing;
    so does a directory that holds only the benchmark's own files."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = ["--workload", "gpt3-760m.pretrain", "--seed", "1", "--seconds",
           "1", "--trace", "0"]
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py")] + cmd,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()
    assert "needs a TPU" in out.stderr

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py")] + cmd
        + ["--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()
