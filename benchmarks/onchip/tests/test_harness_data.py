"""The harness is data: a configuration, a traffic mix, a cell's limits and
a per-layer metric are added as new files plus entries, and the harness
finds, lists and runs them with no edit to a file it already has."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from harness import spec as S

ROOT = Path(__file__).resolve().parents[3]


def _add_cell(tmp: Path) -> dict:
    """New files only: a configuration, a traffic mix, limits and a metric
    reader, under their own root, and a benchmark that names them."""
    for d in ("configs", "traffic", "limits", "metrics"):
        (tmp / d).mkdir()
    cfg = json.loads((S.BENCH_DIR / "configs" / "rp-dragon-stream.json")
                     .read_text())
    cfg["name"] = "dummy-deployment"
    cfg["pilot"]["backends"]["dragon"]["workers"] = 2
    (tmp / "configs" / "dummy-deployment.json").write_text(json.dumps(cfg))
    (tmp / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "kind": "closed_loop", "clients": 3,
        "task": {"payload": "score", "model": "scorer", "batch": 2,
                 "seq_len": 32}}))
    (tmp / "limits" / "dummy.cell.json").write_text(json.dumps(
        {"score": {"sample": 4, "score_gap": 0.05}}))
    (tmp / "metrics" / "dummy_tasks_seen.py").write_text(
        '"""Tasks seen in the window (a dummy per-layer metric)."""\n'
        "from harness.readers import counted\n\n\n"
        "def read(run):\n    return float(len(counted(run)))\n")
    bench = copy.deepcopy(S.load_benchmark())
    bench["configs"].append({"name": "dummy-deployment", "source": "x",
                             "file": "dummy", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.cell",
                               "config": "dummy-deployment",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append("dummy.cell")
    bench["per_layer"].append({
        "name": "dummy_tasks_seen", "unit": "tasks", "better": "higher",
        "source": "host_clock", "layer": "dummy", "moves": "tasks_per_s",
        "workloads": ["dummy.cell"]})
    return bench


def test_new_files_are_listed_and_loaded(tmp_path):
    bench = _add_cell(tmp_path)
    assert "dummy-deployment" in S.available("configs", tmp_path)
    assert "dummy-mix" in S.available("traffic", tmp_path)
    assert "dummy_tasks_seen" in S.available("metrics", tmp_path)
    cell = S.Cell(bench, "dummy.cell", tmp_path)
    assert cell.config["pilot"]["backends"]["dragon"]["workers"] == 2
    assert cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.metrics(trace=True)] == [
        "dummy_tasks_seen"]
    assert cell.reader("dummy_tasks_seen").read is not None
    # the benchmark's own cells are listed from its own files
    for w in S.load_benchmark()["workloads"]:
        c = S.Cell(S.load_benchmark(), w["name"])
        assert c.traffic["kind"] in S.available("drivers")


def test_new_cell_runs(tmp_path, monkeypatch):
    bench = _add_cell(tmp_path)
    monkeypatch.setattr(S, "load_benchmark", lambda path=None: bench)
    import harness.cell as C
    import io
    import time
    from tests.small import shrink
    out, err = io.StringIO(), io.StringIO()
    rc = C.execute("dummy.cell", 12345, 1.0, True, require_tpu=False,
                   root=tmp_path, t_process=time.perf_counter(), out=out,
                   err=err, tweak=shrink)
    assert rc == 0, err.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["metrics"]["dummy_tasks_seen"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/onchip/run.py",
                        "--workload", "stream.short", "--seed", "3",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "needs a TPU" in p.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        S.Cell(S.load_benchmark(), "no.such.cell")


def test_held_cells_load_only_with_the_held_entries():
    bench = S.load_benchmark()
    held = S.with_held(bench)
    names = {w["name"] for w in bench["workloads"]}
    extra = [w["name"] for w in held["workloads"] if w["name"] not in names]
    assert extra == ["campaign.impeccable"]
    with pytest.raises(KeyError):
        S.Cell(bench, "campaign.impeccable")
    cell = S.Cell(held, "campaign.impeccable")
    assert sorted(m["name"] for m in cell.metrics(trace=False)) == [
        "campaign_iter_s", "setup_s"]
    assert len(bench["workloads"]) == len(S.load_benchmark()["workloads"])
