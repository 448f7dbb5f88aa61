"""Loads a cell: BENCHMARK.json entry + configuration file + workload file.

Everything is found by the name BENCHMARK.json gives:

    workloads[].traffic -> benchmark/workloads/<traffic>.json
    configs[].file     -> benchmark/configs/<config>.json
    workload.generator -> benchmark/traffic/<generator>.py      (generate)
    config.driver      -> benchmark/harness/drivers/<driver>.py (run_cell)
    metric name        -> benchmark/layer_metrics/<name>.py     (read)

A later PR adds files and entries and edits none. Stdlib only: the tests
import this on the CPU without JAX.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:           # files found by name import benchmark.*
    sys.path.insert(0, ROOT)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is outside the contract."""


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name starts with a letter, a "
                        "digit or _ and has at most 64 of [A-Za-z0-9_.-]")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} must be 1-16 of "
                        "[A-Za-z0-9_/%.-], no space")
    return unit


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module; SpecError if it is missing."""
    check_name(name, kind)
    path = os.path.join(BENCH_DIR, *kind.split("/"), name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} named {name!r} "
                        f"({os.path.relpath(path, ROOT)} is missing)")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind.replace('/', '_')}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    bound: Optional[float] = None       # end-to-end only
    layer: Optional[str] = None         # per-layer only
    moves: Optional[str] = None         # per-layer only
    workloads: Optional[List[str]] = None

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]       # benchmark/configs/<config>.json
    traffic: Dict[str, Any]      # benchmark/workloads/<name>.json
    end_to_end: List[Metric]     # those this cell reports
    per_layer: List[Metric]
    run_seconds: int
    generator: Any = None        # benchmark/traffic/<generator>.py
    driver: Any = None           # benchmark/harness/drivers/<driver>.py


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"), "benchmark")


def _metrics(bench: dict) -> Dict[str, List[Metric]]:
    out: Dict[str, List[Metric]] = {"end_to_end": [], "per_layer": []}
    seen = set()
    for group in out:
        for m in bench.get(group, []):
            name = check_name(m.get("name"), f"{group} metric")
            if name in seen:
                raise SpecError(f"metric {name!r} appears twice")
            seen.add(name)
            check_unit(m.get("unit"), f"metric {name}")
            if m.get("better") not in ("lower", "higher"):
                raise SpecError(f"metric {name}: better must be lower|higher")
            if m.get("source") not in SOURCES:
                raise SpecError(f"metric {name}: source must be one of "
                                f"{SOURCES}")
            if group == "end_to_end" and m["source"] not in (
                    "host_clock", "device_trace"):
                raise SpecError(f"end-to-end metric {name}: source must be "
                                "host_clock or device_trace")
            out[group].append(Metric(
                name=name, unit=m["unit"], better=m["better"],
                source=m["source"], bound=m.get("bound"),
                layer=m.get("layer"), moves=m.get("moves"),
                workloads=m.get("workloads")))
    e2e = {m.name for m in out["end_to_end"]}
    if "setup_s" not in e2e:
        raise SpecError("end_to_end must hold setup_s")
    for m in out["per_layer"]:
        if m.moves not in e2e:
            raise SpecError(f"per-layer metric {m.name} moves {m.moves!r}, "
                            "which is no end-to-end metric")
    return out


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` with its files read and every reference resolved.
    Raises SpecError for a missing configuration, generator, driver or
    per-layer reader, and for a name or unit outside the allowed letters."""
    bench = load_benchmark(root)
    metrics = _metrics(bench)
    cells = {w.get("name"): w for w in bench.get("workloads", [])}
    for w in cells:
        check_name(w, "workload")
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have: {sorted(cells)})")
    entry = cells[name]
    if entry.get("chips") not in (1, 4):
        raise SpecError(f"workload {name}: chips must be 1 or 4")
    configs = {c.get("name"): c for c in bench.get("configs", [])}
    cname = check_name(entry.get("config"), "config")
    check_name(entry.get("traffic"), "traffic")
    if cname not in configs:
        raise SpecError(f"workload {name} names configuration {cname!r}, "
                        "which BENCHMARK.json does not list")
    config = _read_json(os.path.join(root, configs[cname]["file"]),
                        f"configuration {cname}")
    traffic = _read_json(
        os.path.join(BENCH_DIR, "workloads", entry["traffic"] + ".json"),
        f"traffic {entry['traffic']}")
    if traffic.get("config", cname) != cname:
        raise SpecError(f"workloads/{entry['traffic']}.json is written for "
                        f"configuration {traffic.get('config')!r}, the cell "
                        f"names {cname!r}")
    generator = load_module("traffic", traffic.get("generator", ""))
    driver = load_module("harness/drivers", config.get("driver", ""))
    for w in metrics["end_to_end"] + metrics["per_layer"]:
        for c in w.workloads or []:
            if c not in cells:
                raise SpecError(f"metric {w.name} lists unknown workload "
                                f"{c!r}")
    e2e = [m for m in metrics["end_to_end"] if m.reported_in(name)]
    per_layer = [m for m in metrics["per_layer"] if m.reported_in(name)]
    for m in per_layer:
        load_module("layer_metrics", m.name)
        moved = next(e for e in metrics["end_to_end"] if e.name == m.moves)
        if not moved.reported_in(name):
            raise SpecError(f"{m.name} moves {m.moves}, which cell {name} "
                            "does not report")
    if len(e2e) < 2 or not per_layer:
        raise SpecError(f"cell {name} needs setup_s, one more end-to-end "
                        "metric and one per-layer metric")
    return Cell(name=name, chips=entry["chips"], why=entry.get("why", ""),
                config_name=cname, config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench.get("run_seconds", 10)),
                generator=generator, driver=driver)
