"""chip_smoke.py: its serving body on the CPU at tiny size, with the
interpret-mode engine named explicitly (the script's entry point accepts
only a TPU, so the harness is tested without a fallback), and the entry
point refusing a host without one."""

import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_serves_and_recovers_exactly(chip_smoke):
    bulk = 32
    rec = chip_smoke.serve_preset(
        "hera-80", ["--engine", "pallas-interpret", "--window", "8"],
        bulk=bulk)
    assert rec["engine"] == "pallas-interpret"
    small = chip_smoke.SMALL_BLOCKS
    tenants = len(chip_smoke.TENANTS)
    # per tenant: both directions per small size, the bulk request, the
    # rotation, then one request each way on the rotated nonce
    assert rec["requests"] == tenants * (2 * len(small) + 4)
    assert rec["blocks"] == tenants * (2 * sum(small) + bulk + small[0]
                                       + small[-1])
    assert rec["windows"] >= tenants * bulk // 8


@pytest.mark.parametrize("reply,why", [
    ({"ok": False, "shed": True}, "shed"),
    ({"ok": False, "error": "saturated"}, "saturated"),
    ({"ok": False, "error": "RuntimeError: Mosaic failed"}, "Mosaic"),
])
def test_smoke_rejects_every_non_ok_reply(chip_smoke, reply, why):
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke._require_ok(reply, "tenant-a inbound 1 blocks")


def test_entry_point_refuses_a_host_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out
