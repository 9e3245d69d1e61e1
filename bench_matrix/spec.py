"""Finds everything that belongs to one cell by name.

A cell (`workloads/<name>.json`) names its configuration
(`configs/<name>.json`), its traffic mix (`traffic/<name>.json`), its runner
(`runners/<name>.py`) and the metrics it reports; a per-layer metric
(`layer_metrics/<name>.json`) names its reader (`readers/<name>.py`). A later
PR adds files and edits none: nothing here knows a cell, a configuration, a
mix or a metric by name.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """A data file is missing, malformed or refers to nothing."""


def load(kind: str, name: str, root: Path = ROOT) -> dict:
    """The JSON object in `<root>/<kind>/<name>.json`."""
    if not NAME.match(name):
        raise SpecError(f"{kind} name {name!r} has characters a name may not")
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} named {name!r}: {path} is not a file")
    with open(path) as f:
        out = json.load(f)
    if not isinstance(out, dict):
        raise SpecError(f"{path} does not hold a JSON object")
    return out


def names(kind: str, root: Path = ROOT) -> list:
    return sorted(p.stem for p in (root / kind).glob("*.json"))


def load_cell(name: str, root: Path = ROOT) -> dict:
    """A cell with its configuration, traffic and metric files resolved."""
    cell = dict(load("workloads", name, root), name=name)
    for key in ("config", "traffic", "runner", "chips", "why", "end_to_end",
                "per_layer", "correctness"):
        if key not in cell:
            raise SpecError(f"cell {name!r} lacks {key!r}")
    if cell["chips"] not in (1, 4):
        raise SpecError(f"cell {name!r} asks for {cell['chips']} chips")
    cell["config_name"], cell["traffic_name"] = cell["config"], cell["traffic"]
    cell["config"] = load("configs", cell["config_name"], root)
    cell["traffic"] = load("traffic", cell["traffic_name"], root)
    cell["per_layer"] = {
        m: load("layer_metrics", m, root) for m in cell["per_layer"]
    }
    if "setup_s" not in cell["end_to_end"]:
        raise SpecError(f"cell {name!r} does not report setup_s")
    return cell


def module(package: str, name: str):
    """`bench_matrix.<package>.<name>`: a runner, trainer or reader."""
    if not NAME.match(name):
        raise SpecError(f"{package} name {name!r} is not a name")
    return importlib.import_module(f"bench_matrix.{package}.{name}")


def resolve(path: str):
    """'package.module:attribute' -> the attribute."""
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)
