"""Everything that belongs to one cell is found by NAME, from data.

``BENCHMARK.json`` (one directory above this one) lists the cells; a
cell names a configuration and a traffic mix.  This module turns those
names into files and has no table of its own:

    configs/<config>.json          the deployment as it is run
    traffic/<traffic>.json         the parameters of the event mix
    jobs/<job>.py                  build(env, source, sink, config)
    references/<reference>.py      check(config, emitted, results)
    sources/<source>.py            make(config, traffic, seed, seconds)
    generators/<dist>.py           draw(rng, n, key_space, params)
    layer_metrics/<metric>.py      read(run) -> float | None

The configuration names its job and its reference, the traffic mix its
source and its key distribution.

A later PR adds a cell by adding files and ``BENCHMARK.json`` entries;
it edits nothing that is here.  Imports nothing but the standard
library, so the tests can use it without the system.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CONTRACT = BENCH_DIR.parent / "BENCHMARK.json"


class CellError(Exception):
    """The cell, or a file it names, cannot be resolved."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: metric entries of BENCHMARK.json that apply to this cell
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def read_json(path: Path) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"no such file: {path}") from None
    except json.JSONDecodeError as e:
        raise CellError(f"{path}: {e}") from None


def applies(metric: dict, cell_name: str) -> bool:
    """A metric with no ``workloads`` key exists in every cell."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, bench_dir: Path = BENCH_DIR,
              contract: Path | None = None, rehearsal: bool = False) -> Cell:
    contract = read_json(contract or bench_dir.parent / CONTRACT.name)
    entry = next((w for w in contract["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise CellError(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{[w['name'] for w in contract['workloads']]}")
    config = read_json(bench_dir / "configs" / f"{entry['config']}.json")
    traffic = read_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    if rehearsal:
        # the tiny sizes of a CPU rehearsal are data too
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in contract["end_to_end"]
                    if applies(m, workload)],
        per_layer=[m for m in contract["per_layer"]
                   if applies(m, workload)])


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module of its own (no
    package, no sys.path entry: two kinds may reuse a name)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
