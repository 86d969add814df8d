"""Find a cell and everything it names, by name.

``BENCHMARK.json`` lists the cells.  A cell's ``config`` is
``configs/<config>.json``, its ``traffic`` is ``traffic/<traffic>.json``,
and its own parameters, where it has any (an open loop's rate), are in
``cells/<cell>.json``.  A metric ``m`` is read by ``metrics/<m>.py``,
whose ``read(ctx)`` returns a number, or None where it finds nothing to
read.  Adding a configuration, a mix, a metric or a cell is adding files
and entries; nothing here names one.  Files are looked up in a list of
directories, first match winning, so a test can bring cells of its own
and still use the benchmark's mixes and readers.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    params: dict
    end_to_end: list        # the end-to-end metric entries this cell reports
    per_layer: list         # the per-layer metric entries this cell reports
    dirs: tuple             # where its files were looked up


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def find(dirs, *parts) -> pathlib.Path:
    """The first ``dir/parts`` of ``dirs`` that exists."""
    for d in dirs:
        path = pathlib.Path(d).joinpath(*parts)
        if path.exists():
            return path
    raise FileNotFoundError(f"{'/'.join(parts)} in none of "
                            f"{[str(d) for d in dirs]}")


def load_cell(name: str, benchmark: pathlib.Path,
              dirs=(BENCH_DIR,)) -> Cell:
    """The cell ``name`` of the benchmark file ``benchmark``, with the
    files it names looked up in ``dirs``."""
    dirs = tuple(pathlib.Path(d) for d in dirs)
    bench = _read_json(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in {benchmark}; have "
                       f"{sorted(cells)}")
    w = cells[name]
    config = _read_json(find(dirs, "configs", f"{w['config']}.json"))
    mix = _read_json(find(dirs, "traffic", f"{w['traffic']}.json"))
    try:
        params = _read_json(find(dirs, "cells", f"{name}.json"))
    except FileNotFoundError:
        params = {}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                params=params, end_to_end=e2e, per_layer=per_layer,
                dirs=dirs)


def load_reader(metric: str, dirs=(BENCH_DIR,)):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = find(dirs, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR
               ) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _read_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
