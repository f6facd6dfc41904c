"""``BENCHMARK.json`` keeps to the shape the check reads, and every part it
names is a file the harness finds."""
import json
import re

from harness import spec as S

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_shape_and_names():
    b = S.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(b["paths"][0] + "/")
        assert (S.REPO_ROOT / c["file"]).is_file()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough():
    b = S.with_held(S.load_benchmark())
    for w in b["workloads"]:
        cell = S.Cell(b, w["name"])
        e2e = [m["name"] for m in cell.metrics(trace=False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics(trace=True)
        assert layer
        for m in layer:
            assert m["moves"] in e2e
            assert callable(cell.reader(m["name"]).read)
        for m in cell.metrics(trace=False):
            assert callable(cell.reader(m["name"]).read)
        assert cell.limits
