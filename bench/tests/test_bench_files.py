"""The benchmark's files: everything is found by name, every metric is
reported where it says, and a new cell or metric needs only new files."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    ctx = run.load_cell(ROOT, cell)
    assert ctx.config["name"] == ctx.cell["config"]
    assert os.path.isfile(os.path.join(
        ROOT, "bench", "paths", ctx.traffic["path"] + ".py"))
    assert ctx.limits and all(v > 0 for v in ctx.limits.values())


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads_by_name(metric):
    assert callable(run.reader(ROOT, metric))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(config):
    data = json.load(open(os.path.join(ROOT, config["file"])))
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert any(c["config"] == config["name"] for c in BENCH["workloads"])


def test_metric_workloads_report_what_they_move():
    for m in BENCH["per_layer"]:
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            reported = {e["name"] for e in run.end_to_end(BENCH, cell)}
            assert m["moves"] in reported, (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in run.end_to_end(BENCH, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.per_layer(BENCH, cell)


def test_benchmark_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    assert {c["config"] for c in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}
    # every file the command and the configurations name lies under paths
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")


def test_new_cell_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "minicpm-2b.prefill-1x4k", "config": "minicpm-2b",
        "traffic": "prefill-1x4k", "chips": 1, "why": "longer prompts"})
    bench["end_to_end"][2]["workloads"].append("minicpm-2b.prefill-1x4k")
    bench["per_layer"].append({
        "name": "busy_ms.prefill", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "prefill_ms",
        "workloads": ["minicpm-2b.prefill-1x4k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.load(open(os.path.join(
        ROOT, "bench", "traffic", "prefill-1x2k.json")))
    traffic["prompt"] = 4096
    (tmp_path / "bench" / "traffic" / "prefill-1x4k.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "workloads" /
     "minicpm-2b.prefill-1x4k.json").write_text(
        json.dumps({"limits": {"logits_rel": 0.1}}))
    (tmp_path / "bench" / "metrics" / "busy_ms.prefill.py").write_text(
        "def read(rec):\n    return rec['busy_s'] / rec['steps'] * 1e3\n")
    before = {p: open(os.path.join(ROOT, "bench", p)).read()
              for p in ("run.py", "trace.py", "shares.py")}
    ctx = run.load_cell(str(tmp_path), "minicpm-2b.prefill-1x4k")
    assert ctx.traffic["prompt"] == 4096
    mine = [m["name"] for m in run.per_layer(bench, ctx.cell["name"])]
    assert mine == ["busy_ms.prefill"]
    read = run.reader(str(tmp_path), "busy_ms.prefill")
    assert read({"busy_s": 0.5, "steps": 10}) == 50.0
    assert before == {p: open(os.path.join(ROOT, "bench", p)).read()
                      for p in before}


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_on_cpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
