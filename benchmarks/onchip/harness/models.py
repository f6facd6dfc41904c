"""A configuration's models: the system's own config object built from the
sizes in the configuration file, and the sizes themselves for the plain
reference."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


class Model:
    def __init__(self, name: str, entry: Dict[str, Any]):
        from repro.configs import get_config
        self.name = name
        self.entry = entry
        self.m = dict(entry["sizes"])          # what the reference reads
        self.family = entry["family"]
        base = get_config(entry["arch"])
        fields = {f.name for f in dataclasses.fields(base)}
        unknown = sorted(set(self.m) - fields)
        if unknown:
            raise KeyError(f"model {name}: sizes the system does not know: "
                           f"{unknown}")
        self.cfg = dataclasses.replace(base, **self.m)
