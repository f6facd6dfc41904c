"""Find a cell's parts by name: its configuration, traffic mix, limits and
metric readers, each a file of its own under the benchmark's directory."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
# BENCHMARK.json sits at the root of the checkout, two levels above
REPO_ROOT = BENCH_DIR.parents[1]


def load_benchmark(path: Optional[Path] = None) -> Dict[str, Any]:
    path = path or REPO_ROOT / "BENCHMARK.json"
    return json.loads(Path(path).read_text())


def with_held(bench: Dict[str, Any], root: Path = BENCH_DIR
              ) -> Dict[str, Any]:
    """``bench`` with the cells held out of it added: each file under
    ``held/`` holds the entries of one cell that ``BENCHMARK.json`` leaves
    out until the program can be measured there (its ``why`` says why).
    Tests and tools read them; ``run.py`` does not."""
    out = {k: list(v) if isinstance(v, list) else v for k, v in bench.items()}
    for path in sorted((root / "held").glob("*.json")):
        part = json.loads(path.read_text())
        for k in ("configs", "workloads", "end_to_end", "per_layer"):
            out[k] = out[k] + part.get(k, [])
    return out


def _load_json(kind: str, name: str, root: Path = BENCH_DIR) -> Dict[str, Any]:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, root: Path = BENCH_DIR):
    """Load ``<root>/<kind>/<name>.py``, or the benchmark's own file of that
    name, as a module (names may hold dots)."""
    path = root / kind / f"{name}.py"
    if not path.is_file():
        path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"onchip_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def available(kind: str, root: Path = BENCH_DIR) -> List[str]:
    """Names of the configurations, traffic mixes, limits or metric readers
    (``kind`` is the directory) that files define."""
    suffix = ".py" if kind in ("metrics", "drivers", "payloads", "reference",
                               "checks") else ".json"
    return sorted(p.name[:-len(suffix)] for p in (root / kind).glob(
        f"*{suffix}") if not p.name.startswith("_"))


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, bench: Dict[str, Any], name: str,
                 root: Path = BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.bench = bench
        self.name = name
        self.root = root
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = _load_json("configs", entry["name"], root)
        self.traffic = _load_json("traffic", self.workload["traffic"], root)
        self.limits = _load_json("limits", name, root)

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports: end-to-end ones with ``--trace 0``,
        per-layer ones with ``--trace 1``. A metric without ``workloads`` is
        reported wherever the end-to-end metric it moves is."""
        e2e = self.bench["end_to_end"]

        def mine(m) -> bool:
            if "workloads" in m:
                return self.name in m["workloads"]
            if "moves" in m:
                moved = {x["name"]: x for x in e2e}[m["moves"]]
                return mine(moved)
            return True

        group = self.bench["per_layer"] if trace else e2e
        return [m for m in group if mine(m)]

    def reader(self, metric_name: str):
        return load_module("metrics", metric_name, self.root)
