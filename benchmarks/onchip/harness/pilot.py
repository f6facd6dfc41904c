"""The runtime a configuration describes: one real-engine session with one
pilot, its backends as the configuration's ``pilot`` entry lays them out,
flux partitions carved from a mesh over the run's chips."""
from __future__ import annotations


def build(run):
    from repro.core import (PilotDescription, PilotManager, Session,
                            TaskManager)
    from repro.launch.mesh import make_mesh
    layout = run.cell.config["pilot"]
    backends = {k: dict(v) for k, v in layout["backends"].items()}
    if "flux" in backends:
        backends["flux"]["mesh"] = make_mesh((run.chips, 1), ("data", "model"))
    session = Session(mode="real", seed=run.derive(0))
    pilot = PilotManager(session).submit_pilots(PilotDescription(
        nodes=layout["nodes"], backends=backends))
    tmgr = TaskManager(session)
    tmgr.add_pilots(pilot)
    return session, tmgr


def record(task, payload: str, **kw):
    """What a run keeps of a finished task."""
    return dict(uid=task.uid, payload=payload, state=task.state.value,
                stamps=dict(task.timestamps), result=task.result,
                error=task.error, **kw)
