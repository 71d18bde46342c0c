"""The harness's plumbing on the CPU at a tiny size, and the command's
refusals.  A CPU run measures nothing: these check control flow, files
found by name and the result's shape, never a number of the device."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, weights

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
SEED = 2 ** 31 + 4242


def tiny_cell(kind, seconds=1.5, trace=False, limits=None):
    cfg = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / f"tiny-{kind}.json").read_text())
    spec = {"end_to_end": [], "per_layer": []}
    return harness.Cell(f"tiny.{kind}", spec, {"chips": 1}, cfg, mix,
                        limits or {}, SEED, seconds, trace)


def test_spec_names_files_that_exist():
    spec = harness.load_spec()
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "configs" / f"{cfg['reference']}.py").exists()
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names
    for w in spec["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / f"{mix['kind']}_cell.py").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
        cell = harness.find_cell(w["name"], seed=1, seconds=1, trace=True)
        assert cell.per_layer(), w["name"]
        assert {m["name"] for m in cell.end_to_end()} >= {"setup_s", "peak_hbm_gib"}
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in names


def test_program_layout_weights_equal_the_references_rows():
    import jax
    import jax.numpy as jnp
    m = json.loads((DATA / "tiny.json").read_text())
    p = weights.program_params(SEED, m)
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    by = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): v
          for path, v in flat}
    assert set(by) == set(weights.PATHS.values())
    for name, path in weights.PATHS.items():
        want = by[path] if name in weights.TOP_LEAVES else by[path][jnp.array([1, 0])]
        assert bool(jnp.array_equal(weights.start(SEED, m, name, [1, 0]), want)), name
    assert set(weights.change_norms(SEED, m, [1, 0], {
        n: weights.start(SEED, m, n, [1, 0]) for n in weights.PATHS}).values()) == {0.0}
    other = weights.program_params(SEED + 1, m)
    assert not bool(jnp.array_equal(other["head"], p["head"]))


def test_cpu_rehearsal_reports_no_device_metric():
    cell = tiny_cell("train", limits={"loss_gap": 1.0, "grad_gap": 1.0,
                                      "change_gap": 1.0})
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    line = harness.result_line(cell, out, {"platform": "cpu"})
    assert line["correct"] is True, line
    assert line["metrics"] == {}          # no cell metric on the CPU
    assert list(line)[-1] == "checks"
    assert out.attempted > 0 and out.failed == 0
    assert out.setup_s > 0


def test_cpu_rehearsal_traced_reduces_its_trace():
    cell = tiny_cell("train", trace=True)
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    tr = out.ctx["trace"]
    assert tr["window_s"] == pytest.approx(cell.seconds, rel=0.5)
    assert out.ctx["steps"] > 0
    # the window's spans only, one "data" span a step
    data = [s for s in out.ctx["spans"] if s[0] == "data"]
    assert len(data) == out.ctx["steps"]


def _run(cmd, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_command_refuses_a_cpu():
    spec = harness.load_spec()
    name = spec["workloads"][0]["name"]
    r = _run([sys.executable, "bench/run.py", "--workload", name, "--seed",
              str(SEED), "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_command_refuses_without_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    name = harness.load_spec()["workloads"][0]["name"]
    r = _run([sys.executable, "bench/run.py", "--workload", name, "--seed",
              "1", "--seconds", "1", "--trace", "0"], tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
