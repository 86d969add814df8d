"""The shape of ``hera-128a.bulk-tensors`` as the benchmark declares it:
the metrics it reports, how its request falls into windows, the
admission limit and frame size it has to fit, and how many of its
requests one session holds before the harness rotates it."""

import asyncio
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import cells, traffic  # noqa: E402
from chipbench.harness import Plane  # noqa: E402
from chipbench.wire import encode  # noqa: E402

CELL = "hera-128a.bulk-tensors"
PER_LAYER = {"producer_us_per_block", "kernel_us_per_block",
             "keystream_kernel_roofline", "device_idle_pct",
             "worker_busy_pct", "worker_host_us_per_block",
             "idle_worker_host_pct", "idle_worker_free_pct"}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL, ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def args(cell):
    from repro.serve import server

    return server.build_parser().parse_args(
        list(cell.config["server_args"]) + ["--port", "0"])


@pytest.fixture(scope="module")
def request_(cell):
    cfg = cell.config
    gen = traffic.Generator(cell.mix, tenants=int(cfg["tenants"]),
                            l=cfg["cipher"]["l"], q=cfg["cipher"]["q"],
                            seed=2**31 + 11)
    return next(gen.closed_stream(0, 0))


def test_cell_is_the_hera_set_on_one_chip(cell):
    spec = cell.config["cipher"]
    assert cell.chips == 1 and cell.config["reduced"] == []
    assert (spec["preset"], spec["n"], spec["l"], spec["rounds"],
            spec["q_bits"]) == ("hera-128a", 16, 16, 5, 28)
    assert cell.mix["loop"] == "closed" and cell.params == {}
    assert cell.mix["size"] == {"words": 24576}


def test_cell_reports_its_metrics(cell):
    assert {m["name"] for m in cell.end_to_end} == {"words_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == PER_LAYER
    assert all(m["moves"] == "words_per_s" for m in cell.per_layer)


def test_request_is_24_full_windows(cell, args, request_):
    assert args.window == 64
    assert request_.words == 24576 and request_.payload.shape == (1536, 16)
    windows, tail = divmod(request_.blocks, args.window)
    assert (windows, tail) == (24, 0)


def test_request_fits_admission_and_frame(cell, args, request_):
    from repro.serve.server import MAX_FRAME

    # a closed loop holds one request per tenant, so its lanes are all
    # that can be pending for the tenant
    assert args.max_pending_lanes == 4096
    assert request_.blocks * cell.mix["connections_per_tenant"] \
        <= args.max_pending_lanes
    frame = encode({"op": "submit", "tenant": "t7", "session": 0,
                    "hhe_op": request_.op, "payload": request_.payload,
                    "id": 1})
    assert len(frame) < MAX_FRAME


class _Conn:
    def __init__(self):
        self.calls = []

    async def call(self, msg):
        self.calls.append(msg["op"])
        return {"ok": True}


def test_session_holds_42_requests_before_a_rotation(cell, request_):
    plane = Plane.__new__(Plane)
    plane.session_blocks = int(cell.config["session_blocks"])
    plane.conns, plane.sessions = [[_Conn()]], [[0]]
    plane.cursor, plane.rotations = [[0]], 0

    async def go():
        for k in range(1, 43):
            await plane.reserve(0, 0, request_.blocks)
            assert plane.rotations == 0, k
        await plane.reserve(0, 0, request_.blocks)

    asyncio.run(go())
    assert plane.session_blocks == 65536
    assert plane.rotations == 1 and plane.conns[0][0].calls == ["rotate"]
    assert plane.cursor == [[request_.blocks]]

