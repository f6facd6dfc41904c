"""The control: the plain reference computed in float8 (one step below the
configurations' bfloat16), put in the system's place, at a size the CPU
holds, read by the same code that reads it on the chip
(``tools/readings.py``). The system's own payload passes every number of
the cell; the control reads at least three times what the system reads on
one of them, the separation the limits are set in. The limits themselves
come from readings at the cells' own sizes on the chip (PERF.md), where the
control fails them; at these sizes float8's error is smaller."""
import time

import pytest

from harness import device as D
from harness import spec as S
from harness.cell import Run
from tests.small import shrink
from tools import readings as R


def _run(workload, seed):
    cell = S.Cell(S.with_held(S.load_benchmark()), workload)
    run = Run(cell, seed, 0, False, D.claim(cell.chips, require_tpu=False),
              time.perf_counter())
    shrink(run)
    return run


@pytest.mark.parametrize("workload,payload", [
    ("stream.short", "score"),
    ("campaign.impeccable", "train"),
    ("campaign.impeccable", "generate"),
])
def test_control_fails_where_the_system_passes(workload, payload):
    run = _run(workload, 2**31 + 101)
    t = run.cell.traffic
    spec = next(s for s in t.get("stages", [t.get("task")])
                if s["payload"] == payload)
    r = R.READERS[payload](run, spec, control=True)
    limits = {k: v for k, v in run.cell.limits[payload].items()
              if k != "sample"}
    assert all(r[k] <= lim for k, lim in limits.items()), r
    assert any(r[k + ".control"] > max(3 * r[k], 1e-6) for k in limits), r
