#!/usr/bin/env python3
"""Serve every cipher preset through the compiled Pallas kernel on one TPU.

    python chip_smoke.py [--presets hera-80,pasta-128l]

Everything runs in this one process, because a chip belongs to one process
at a time.  For each preset in ``repro.core.params.REGISTRY`` the script
builds the tenant registry the server CLI (``python -m
repro.serve.server``) builds with its default flags, warmed up.  It boots
the TCP serving plane on loopback and drives it with two tenants, each a
``ServeClient`` over TCP.  Each tenant sends:

  * 1-4 block requests in both directions;
  * one bulk inbound request of 4096 blocks (64 windows at window 64);
  * one live rotation, then one request in each direction again.

Every request must come back ok and recover its plaintext exactly; the
client checks against the ``ref``-engine single-stream oracle.  Every
tenant's farm must run the ``pallas`` engine, which is also what "auto"
must resolve to for every preset (no tuner cache is consulted).

One line per preset reports set-up figures (warm-up/compile seconds, the
traffic phase's seconds, requests, blocks, recovery): they are not speed
measurements.  The last line is ``{"ok": true, "device": {...}}``.  The
script exits non-zero on any failure, and at once, printing no result,
when JAX's first device is not a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

TENANTS = ("tenant-a", "tenant-b")
SMALL_BLOCKS = (1, 2, 3, 4)
BULK_BLOCKS = 4096
#: a path inside the checkout that never exists: "auto" must pick the
#: engine from the device alone, never from a cached measurement
NO_TUNER_CACHE = ROOT / ".chip_smoke_no_tuner_cache.json"


class SmokeFailure(RuntimeError):
    """A reply or a recovered plaintext that the smoke does not accept."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _require_ok(reply: dict, what: str) -> None:
    """Error, saturated and shed replies are all failures."""
    if reply.get("ok"):
        return
    why = "shed" if reply.get("shed") else reply.get("error", reply)
    raise SmokeFailure(f"{what}: {why}")


async def _drive_tenant(client, rng, small, bulk: int) -> dict:
    import numpy as np

    session = await client.open_session()
    q, l = client.params.mod.q, client.params.l
    sent = {"requests": 0, "blocks": 0}

    async def inbound(blocks: int) -> None:
        toks = rng.integers(0, q, (blocks, l), dtype=np.uint32)
        r = await client.encrypt_to_server(session, toks)
        what = f"{client.tenant} inbound {blocks} blocks"
        _require_ok(r, what)
        _require(np.array_equal(np.asarray(r["result"], np.uint32), toks),
                 f"{what}: plaintext not recovered exactly")
        sent["requests"] += 1
        sent["blocks"] += blocks

    async def outbound(blocks: int) -> None:
        toks = rng.integers(0, q, (blocks, l), dtype=np.uint32)
        r, back = await client.decrypt_from_server(session, toks)
        what = f"{client.tenant} outbound {blocks} blocks"
        _require_ok(r, what)
        _require(np.array_equal(back, toks),
                 f"{what}: plaintext not recovered exactly")
        sent["requests"] += 1
        sent["blocks"] += blocks

    for blocks in small:
        await inbound(blocks)
        await outbound(blocks)
    await inbound(bulk)
    await client.rotate(session)          # raises on a non-ok reply
    sent["requests"] += 1
    await inbound(small[0])
    await outbound(small[-1])
    return sent


def serve_preset(name: str, cli_args=(), *, small=SMALL_BLOCKS,
                 bulk: int = BULK_BLOCKS, tenants=TENANTS,
                 seed: int = 0) -> dict:
    """Serve ``name`` over loopback TCP with the server CLI's registry
    (``cli_args`` are extra server flags) and drive ``tenants`` through
    it.  Raises :class:`SmokeFailure` on any bad reply or recovery;
    returns the set-up figures and counts."""
    import numpy as np

    from repro.core.engine import resolve_engine
    from repro.serve import server

    args = server.build_parser().parse_args(["--cipher", name, *cli_args])
    registry = server.make_registry(args, warmup=True)
    want_engine = resolve_engine(args.engine or "auto",
                                 params=registry.params)

    async def run() -> dict:
        plane = server.ServePlane(registry, host=args.host, port=0)
        host, port = await plane.start()
        clients = [server.ServeClient(host, port, t) for t in tenants]
        try:
            t0 = time.perf_counter()
            for c in clients:             # hello creates + warms the tenant
                await c.connect()
            t1 = time.perf_counter()
            sent = await asyncio.gather(*[
                _drive_tenant(c, np.random.default_rng(seed + i), small,
                              bulk)
                for i, c in enumerate(clients)])
            t2 = time.perf_counter()
        finally:
            for c in clients:
                await c.close()
            await plane.stop()
        stats = registry.stats()["per_tenant"]
        engines = {t: registry.peek(t).server.farm.engine.name
                   for t in tenants}
        _require(all(e == want_engine for e in engines.values()),
                 f"farm engines {engines}, expected {want_engine!r}")
        for t, st in stats.items():
            _require(st["shed"] == 0 and st["rejected"] == 0,
                     f"{t}: {st['shed']} shed, {st['rejected']} rejected")
        return {
            "preset": name, "engine": want_engine, "tenants": len(tenants),
            "requests": sum(s["requests"] for s in sent),
            "blocks": sum(s["blocks"] for s in sent),
            "windows": sum(st["windows_served"] for st in stats.values()),
            "warmup_s": t1 - t0, "traffic_s": t2 - t1,
        }

    return asyncio.run(run())


def _report(rec: dict) -> str:
    return (f"{rec['preset']}: engine={rec['engine']} "
            f"tenants={rec['tenants']} requests={rec['requests']} "
            f"blocks={rec['blocks']} windows={rec['windows']} "
            f"recovery=exact | set-up figures, not speed: "
            f"warmup_s={rec['warmup_s']} traffic_s={rec['traffic_s']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--presets", default=None,
                    help="comma-separated subset (default: every preset)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {platform!r}", file=sys.stderr)
        return 1

    from repro.core.engine import resolve_engine
    from repro.core.params import REGISTRY, get_params
    from repro.serve.server import enable_compile_cache

    os.environ["REPRO_TUNER_CACHE"] = str(NO_TUNER_CACHE)
    if NO_TUNER_CACHE.exists():
        print(f"chip_smoke: {NO_TUNER_CACHE} must not exist",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    presets = args.presets.split(",") if args.presets else sorted(REGISTRY)
    failed = []
    for name in presets:
        try:
            auto = resolve_engine("auto", params=get_params(name))
            _require(auto == "pallas",
                     f"'auto' resolves to {auto!r}, not 'pallas'")
            print(_report(serve_preset(name)), flush=True)
        except Exception:           # report every preset, then fail
            failed.append(name)
            print(f"{name}: FAILED", flush=True)
            traceback.print_exc()
    if failed:
        print(f"chip_smoke: failed presets: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
