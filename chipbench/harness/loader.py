"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell in ``BENCHMARK.json`` is only names: ``config`` -> ``configs/<name>.json``,
``traffic`` -> ``traffic/<name>.json``; the configuration names its generator
(``datagen/<name>.py``) and its plain reference (``reference/<name>.py``);
every ``layer_metrics/<name>.json`` is a per-layer metric, with a reader in
``layer_metrics/<name>.py`` where the generic readers do not do.  A cell has
no file and no overrides of its own: a pair of configuration and traffic mix
appears once in ``BENCHMARK.json``, so what sets a cell apart is in one of
the two files.  Every lookup walks ``roots`` in order, so a later PR (or a
test) adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
from typing import Any, Iterable, Optional, Sequence

CHIPBENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = CHIPBENCH.parent
DEFAULT_ROOTS = (CHIPBENCH,)


def _need(roots: Sequence[pathlib.Path], kind: str, filename: str
          ) -> pathlib.Path:
    for root in roots:
        p = pathlib.Path(root) / kind / filename
        if p.is_file():
            return p
    raise FileNotFoundError(
        f"no {kind}/{filename} under {[str(r) for r in roots]}")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: pathlib.Path):
    """Import one file as a module of its own (no package needed, so a file
    in a directory a later PR adds is found like the ones here)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_file_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: Optional[pathlib.Path] = None) -> dict:
    return load_json(path or REPO / "BENCHMARK.json")


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(has {[w['name'] for w in bench['workloads']]})")


def apply_xla_flags(config: dict) -> None:
    """Put the configuration's ``xla_flags`` into ``XLA_FLAGS``.  XLA reads
    the variable when the backend starts, so this runs before anything that
    may start it: ``load_cell`` calls it before it imports the generator
    and the reference."""
    flags = config.get("xla_flags") or []
    have = os.environ.get("XLA_FLAGS", "").split()
    new = [f for f in flags if f not in have]
    if new:
        os.environ["XLA_FLAGS"] = " ".join(have + new)


def load_cell(workload: dict, roots: Iterable = DEFAULT_ROOTS) -> dict:
    """Everything one run needs, from the names in the workload entry."""
    roots = tuple(roots)
    config = load_json(_need(roots, "configs", workload["config"] + ".json"))
    apply_xla_flags(config)
    traffic = load_json(_need(roots, "traffic", workload["traffic"] + ".json"))
    config = dict(config, params=dict(config["params"],
                                      **traffic.get("params", {})))
    return {
        "name": workload["name"],
        "chips": int(workload.get("chips", 1)),
        "config": config,
        "traffic": traffic,
        "datagen": load_module(
            _need(roots, "datagen", config["datagen"] + ".py")),
        "reference": load_module(
            _need(roots, "reference", config["reference"] + ".py")),
    }


def load_layer_metrics(roots: Iterable = DEFAULT_ROOTS) -> list[dict]:
    """Every ``layer_metrics/*.json`` under the roots, each with its reader
    module where a ``.py`` of the same name sits beside it."""
    out, seen = [], set()
    for root in roots:
        d = pathlib.Path(root) / "layer_metrics"
        if not d.is_dir():
            continue
        for p in sorted(d.glob("*.json")):
            if p.stem in seen:
                continue
            seen.add(p.stem)
            spec: dict[str, Any] = load_json(p)
            if spec["name"] != p.stem:
                raise ValueError(f"{p}: name {spec['name']!r} is not the "
                                 "file's name")
            py = p.with_suffix(".py")
            spec["module"] = load_module(py) if py.is_file() else None
            out.append(spec)
    return out


def load_peaks(device_kind: str) -> dict:
    table = load_json(CHIPBENCH / "harness" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/harness/"
            f"peaks.json (has {sorted(table['devices'])}): no default peak")
    return table["devices"][device_kind]
