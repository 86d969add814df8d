"""The harness finds everything by name: the benchmark's own cells, and a
cell that only a fixture directory defines, with a configuration, a mix,
a cell file and a metric reader of its own."""

import json
import math
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
EXTRA = BENCH / "tests" / "fixtures" / "extra"
sys.path.insert(0, str(BENCH))

from chipbench import cells  # noqa: E402
from chipbench.harness import Context, Sent  # noqa: E402
from chipbench.reference import Cipher  # noqa: E402
from chipbench.traffic import Request  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_names():
    return [w["name"] for w in benchmark()["workloads"]]


@pytest.mark.parametrize("name", cell_names())
def test_every_cell_loads_with_its_files(name):
    cell = cells.load_cell(name, ROOT / "BENCHMARK.json")
    assert cell.config["name"] == name.split(".")[0]
    assert cell.mix["loop"] in ("open", "closed")
    if cell.mix["loop"] == "open":
        assert cell.params["rate_per_s"] > 0
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.load_reader(m["name"]))


def test_fixture_only_cell_loads_and_reads_its_own_metric():
    cell = cells.load_cell("hera-tiny.tiny-open", EXTRA / "BENCHMARK.json",
                           dirs=(EXTRA, BENCH))
    assert cell.config["tenants"] == 2
    assert cell.params == {"rate_per_s": 40}
    assert [m["name"] for m in cell.end_to_end] == ["p50_ms", "setup_s"]
    # no `workloads` key: reported where the metric it moves is
    assert [m["name"] for m in cell.per_layer] == ["blocks_per_request"]
    bulk = cells.load_cell("hera-tiny.tiny-bulk", EXTRA / "BENCHMARK.json",
                           dirs=(EXTRA, BENCH))
    assert [m["name"] for m in bulk.per_layer] == ["window_occupancy_pct"]

    q, l = cell.config["cipher"]["q"], cell.config["cipher"]["l"]
    import numpy as np

    sent = []
    for i, blocks in enumerate((1, 2, 2, 1)):
        req = Request(i, 0, 0, "decrypt_tokens",
                      np.zeros((blocks, l), np.uint32), blocks * l, 0.01 * i)
        s = Sent(req, due=float(i), sent=float(i), done=float(i) + 0.004,
                 reply={"ok": True, "latency_ms": 3.0})
        sent.append(s)
    ctx = Context(cell=cell, seconds=4.0, setup_s=1.5, window=16, sent=sent,
                  t0=0.0, t_end=4.0, stats_before={}, stats_after={},
                  cipher=Cipher(cell.config["cipher"]), peaks={},
                  drain_limit=64.0)
    read = cells.load_reader("blocks_per_request", cell.dirs)
    assert read(ctx) == 1.5
    assert math.isclose(cells.load_reader("p50_ms", cell.dirs)(ctx), 4.0)
    assert cells.load_reader("setup_s", cell.dirs)(ctx) == 1.5
    assert q > 0


def test_unknown_device_kind_is_an_error():
    assert cells.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        cells.load_peaks("TPU v9 imaginary")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no cell"):
        cells.load_cell("nope.nothing", ROOT / "BENCHMARK.json")


def test_benchmark_file_keeps_the_contract_shape():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert 1 <= b["run_seconds"] <= 51
    for word in b["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
    cfg_names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in cfg_names
        cfg_names.add(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
    pairs, chips4 = set(), 0
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in cfg_names
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        chips4 += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200
    assert chips4 <= max(1, len(b["workloads"]) // 2)
    names = set()
    cells_by_e2e = {}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        every = [w["name"] for w in b["workloads"]]
        cells_by_e2e[m["name"]] = set(m.get("workloads", every))
    assert "setup_s" in cells_by_e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in cells_by_e2e
        assert set(m["workloads"]) <= cells_by_e2e[m["moves"]]
    for w in b["workloads"]:
        reports = [n for n, ws in cells_by_e2e.items() if w["name"] in ws]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(w["name"] in m["workloads"] for m in b["per_layer"])
    assert len(json.dumps(b)) <= 64 * 1024
