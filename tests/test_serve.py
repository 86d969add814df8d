"""Serving plane: event-driven scheduler edges, admission control, tenant
registry safety, rotation under concurrency, and the TCP front end.

The headline contract (ISSUE 10 acceptance): the event-driven scheduler
serves byte-identical ciphertext to a direct `CipherBatch` carve of the
same (session, counter) lanes, for every cipher kind — firing windows on
fill/deadline edges changes WHEN lanes materialize, never WHAT they hold.
"""

import asyncio
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cipher import Cipher, CipherBatch, decode_fixed, encode_fixed
from repro.serve.hhe_loop import (
    OPS,
    HHERequest,
    HHEServer,
    HHEServerSaturated,
    _Entry,
)
from repro.serve.tenants import TenantRegistry, derive_tenant_key

KINDS = ["hera-80", "rubato-128s", "pasta-128s"]   # one preset per cipher


# ---------------------------------------------------------------------------
# Event-driven scheduler edges
# ---------------------------------------------------------------------------
def test_deadline_fires_part_full_window():
    """A part-full window must fire once the oldest lane crosses
    deadline_s — tail requests are never parked behind an unfilled
    window."""
    cb = CipherBatch("hera-80", seed=1)
    srv = HHEServer(cb, window=8, engine="jax", deadline_s=0.05)
    s = srv.open_session()
    srv.submit(HHERequest(session_id=s.index, op="keystream", blocks=3))
    # young lanes: the deadline has not tripped, nothing materializes
    assert srv.service(now=time.perf_counter()) == []
    assert srv.pending_lanes() == 3 and srv.windows_served == 0
    assert srv.next_due() is not None
    # the timer edge: well past the deadline, the partial window fires
    (resp,) = srv.service(now=time.perf_counter() + 1.0)
    assert resp.result.shape == (3, cb.params.l)
    stats = srv.latency_stats()
    assert stats["deadline_fires"] == 1 and stats["windows_served"] == 1
    assert srv.pending_lanes() == 0


def test_fill_fires_inside_submit():
    """fire_on_fill: the submit that fills a window dispatches it — no
    flush() needed for full windows."""
    cb = CipherBatch("hera-80", seed=2)
    srv = HHEServer(cb, window=4, engine="jax", depth=1)
    s = srv.open_session()
    srv.submit(HHERequest(session_id=s.index, op="keystream", blocks=4))
    # depth=1: the fill-fired window was pushed AND consumed synchronously
    assert srv.latency_stats()["fill_fires"] == 1
    assert srv.windows_served == 1
    (resp,) = srv.pop_completed()
    assert resp.result.shape == (4, cb.params.l)


def test_flush_short_circuits_when_idle():
    """The satellite bugfix: a drained server never dispatches an empty
    window, and latency_stats is fully populated before any traffic."""
    cb = CipherBatch("hera-80", seed=3)
    srv = HHEServer(cb, window=4, engine="jax")
    stats = srv.latency_stats()
    assert stats == {
        "count": 0, "p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0,
        "queue_depth_lanes": 0, "inflight_lanes": 0, "windows_served": 0,
        "fill_fires": 0, "deadline_fires": 0, "shed": 0, "rejected": 0,
        "overlapped_reads": 0,
    }
    assert srv.flush() == []
    assert srv.windows_served == 0          # no empty-window dispatch
    assert not srv.busy()


# ---------------------------------------------------------------------------
# Read-back order: a window is read only once later work is queued
# ---------------------------------------------------------------------------
def _mixed_run(depth: int):
    """All five ops with ragged sizes, an auto-rotation and a deadline
    fire through one server at ``depth``.  Returns the responses by seq,
    the order of producer dispatches, consumer dispatches and reads (each
    with the window's serial; reads also say whether a drain made them),
    and the server."""
    import repro.core.cipher as cipher_mod

    cb = CipherBatch("hera-80", seed=41)
    srv = HHEServer(cb, window=4, engine="jax", depth=depth,
                    deadline_s=10.0)
    s0, s1 = srv.open_session(), srv.open_session()
    plans, events, draining = [], [], [False]
    produce, consume = srv.farm.produce, srv.farm.consume
    materialize, drain = srv._materialize, srv._drain

    def serial(plan):
        return next(i for i, p in enumerate(plans) if p is plan)

    def rec_produce(plan, *args):
        plans.append(plan)
        events.append(("produce", serial(plan)))
        return produce(plan, *args)

    def rec_consume(consts):
        events.append(("consume", sum(e[0] == "consume" for e in events)))
        return consume(consts)

    def rec_materialize(plan, *args):
        events.append(("read", serial(plan), draining[0]))
        return materialize(plan, *args)

    def rec_drain():
        draining[0] = True
        try:
            drain()
        finally:
            draining[0] = False

    rng = np.random.default_rng(42)
    q, l = cb.params.mod.q, cb.params.l
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cipher_mod, "SESSION_CTR_LIMIT", 24)
        mp.setattr(srv.farm, "produce", rec_produce)
        mp.setattr(srv.farm, "consume", rec_consume)
        mp.setattr(srv, "_materialize", rec_materialize)
        mp.setattr(srv, "_drain", rec_drain)
        # s0 takes 11 of its 24 counters, so its 14-block request rotates
        for sess, op, blocks in [
                (s0, "keystream", 3), (s1, "encrypt", 5), (s0, "decrypt", 2),
                (s1, "encrypt_tokens", 7), (s0, "decrypt_tokens", 6),
                (s1, "keystream", 9), (s0, "encrypt_tokens", 14),
                (s0, "decrypt", 3)]:
            if op == "keystream":
                payload = None
            elif op == "encrypt":
                payload = rng.uniform(-8, 8, (blocks, l)).astype(np.float32)
            else:
                payload = rng.integers(0, q, (blocks, l), dtype=np.uint32)
            srv.submit(HHERequest(session_id=sess.index, op=op,
                                  payload=payload, blocks=blocks))
        assert cb.sessions[s0.index].generation == 1
        responses = srv.service(now=time.perf_counter() + 60.0)
    assert srv.pending_lanes() == 0 and not srv._ready
    assert srv.latency_stats()["deadline_fires"] == 1
    assert len(responses) == 8
    return {r.seq: r for r in responses}, events, srv


@pytest.fixture(scope="module")
def serialized_run():
    return _mixed_run(1)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_windows_are_read_behind_later_work(depth, serialized_run):
    """Depth 1 produces, consumes and reads each window in turn.  At a
    deeper FIFO every read outside a drain starts after a later window's
    producer was dispatched behind the read window's consumer, so the
    chip has work queued through the read; the served bytes stay those
    of the serialized run, for every op."""
    responses, events, srv = _mixed_run(depth)
    n = srv.windows_served
    assert sorted(e[1] for e in events if e[0] == "read") == list(range(n))
    if depth == 1:
        assert events == [e for k in range(n) for e in
                          (("produce", k), ("consume", k), ("read", k, False))]
        assert srv.overlapped_reads == 0
    else:
        at = {e[:2]: i for i, e in enumerate(events)}
        outside = [e[1] for e in events if e[0] == "read" and not e[2]]
        assert outside
        for k in outside:
            assert any(at[("consume", k)] < at[("produce", j)]
                       < at[("read", k)] for j in range(k + 1, n)), k
        assert srv.overlapped_reads >= len(outside)
    want, _, _ = serialized_run
    assert responses.keys() == want.keys()
    for seq, r in responses.items():
        assert r.result.dtype == want[seq].result.dtype
        np.testing.assert_array_equal(r.result, want[seq].result)
        np.testing.assert_array_equal(r.block_ctrs, want[seq].block_ctrs)


@pytest.mark.parametrize("name, delta", [("hera-80", 1024.0),
                                         ("rubato-128s", 1000.0)])
@pytest.mark.parametrize("op", OPS)
def test_respond_is_bit_exact_with_the_device_path(name, delta, op):
    """``_respond`` computes on the host and returns NumPy arrays equal,
    bit for bit and in dtype, to the eager device ops it replaced, edge
    values 0 and q-1 included, for a scale that is no power of two."""
    cb = CipherBatch(name, seed=51)
    srv = HHEServer(cb, window=4, engine="jax")
    mod, l = cb.params.mod, cb.params.l
    q = mod.q
    rng = np.random.default_rng(52)
    rows = rng.integers(0, q, (6, l), dtype=np.uint32)
    rows[0, :2] = [0, q - 1]
    rows[1, :2] = [q - 1, 0]
    payload = None
    if op == "encrypt":
        top = (q // 2 - 1) / delta          # the largest encodable value
        payload = rng.uniform(-top, top, (6, l)).astype(np.float32)
        payload[0, :3] = [0.0, top, -top]
    elif op != "keystream":
        payload = rng.integers(0, q, (6, l), dtype=np.uint32)
        payload[0, :2] = [0, q - 1]
        payload[1, :2] = [0, q - 1]
    req = HHERequest(session_id=0, op=op, payload=payload, blocks=6,
                     delta=delta)
    entry = _Entry(seq=0, req=req, ctrs=np.arange(6), t_submit=0.0,
                   rows=rows, remaining=0)
    got = srv._respond(entry, 1.0).result

    z = jnp.asarray(rows)
    if op == "keystream":
        want = rows
    elif op == "encrypt":
        want = mod.add(encode_fixed(mod, payload, delta), z)
    elif op == "encrypt_tokens":
        want = mod.add(jnp.asarray(payload, jnp.uint32), z)
    elif op == "decrypt_tokens":
        want = mod.sub(jnp.asarray(payload, jnp.uint32), z).astype(jnp.int32)
    else:
        want = decode_fixed(mod, mod.sub(jnp.asarray(payload, jnp.uint32), z),
                            delta)
    want = np.asarray(want)
    assert type(got) is np.ndarray
    assert got.dtype == want.dtype and got.shape == (6, l)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Admission control / backpressure
# ---------------------------------------------------------------------------
def test_backpressure_reject_at_bound():
    cb = CipherBatch("hera-80", seed=4)
    srv = HHEServer(cb, window=4, engine="jax", fire_on_fill=False,
                    max_pending_lanes=8, overload="reject")
    s = srv.open_session()
    srv.submit(HHERequest(session_id=s.index, op="keystream", blocks=8))
    ctr_before = cb.sessions[s.index].next_ctr
    with pytest.raises(HHEServerSaturated, match="max_pending_lanes"):
        srv.submit(HHERequest(session_id=s.index, op="keystream", blocks=1))
    # a rejected request leaves NO trace in the counter space
    assert cb.sessions[s.index].next_ctr == ctr_before
    assert srv.latency_stats()["rejected"] == 1
    # draining reopens admission
    assert len(srv.flush()) == 1
    assert srv.submit(
        HHERequest(session_id=s.index, op="keystream", blocks=1)) is not None


def test_backpressure_shed_at_bound():
    cb = CipherBatch("hera-80", seed=5)
    srv = HHEServer(cb, window=4, engine="jax", fire_on_fill=False,
                    max_pending_lanes=8, overload="shed")
    s = srv.open_session()
    srv.submit(HHERequest(session_id=s.index, op="keystream", blocks=8))
    ctr_before = cb.sessions[s.index].next_ctr
    assert srv.submit(
        HHERequest(session_id=s.index, op="keystream", blocks=2)) is None
    assert cb.sessions[s.index].next_ctr == ctr_before
    stats = srv.latency_stats()
    assert stats["shed"] == 1 and stats["queue_depth_lanes"] == 8
    # the buffered work still serves exactly
    (resp,) = srv.flush()
    assert resp.result.shape[0] == 8


def test_pending_bound_validation():
    cb = CipherBatch("hera-80", seed=6)
    with pytest.raises(ValueError, match="below one window"):
        HHEServer(cb, window=8, engine="jax", max_pending_lanes=4)
    with pytest.raises(ValueError, match="overload policy"):
        HHEServer(cb, window=4, engine="jax", overload="drop-newest")


# ---------------------------------------------------------------------------
# Tenant registry
# ---------------------------------------------------------------------------
def test_tenant_keys_distinct_and_deterministic():
    k1 = derive_tenant_key("hera-80", "alice", seed=0)
    k2 = derive_tenant_key("hera-80", "bob", seed=0)
    assert not np.array_equal(k1, k2)
    np.testing.assert_array_equal(
        k1, derive_tenant_key("hera-80", "alice", seed=0))
    reg = TenantRegistry("hera-80", capacity=4, window=4, engine="jax")
    np.testing.assert_array_equal(np.asarray(reg.get("alice").batch.key), k1)


def test_eviction_never_drops_in_flight_tenants():
    """The LRU bound must not corrupt live streams: busy tenants are
    skipped, and when everyone is busy the registry grows instead."""
    reg = TenantRegistry("hera-80", capacity=2, window=4, engine="jax",
                         fire_on_fill=False)
    t1, t2 = reg.get("t1"), reg.get("t2")
    for t in (t1, t2):
        s = t.server.open_session()
        t.server.submit(HHERequest(session_id=s.index, blocks=2))
    # both over-capacity candidates are busy -> grow, never evict
    reg.get("t3")
    assert len(reg) == 3 and reg.evictions == 0 and reg.busy_overflows == 1
    assert "t1" in reg and "t2" in reg
    # explicit eviction refuses busy tenants too
    with pytest.raises(RuntimeError, match="in-flight"):
        reg.evict("t1")
    # drained + collected -> t1 is the LRU idle candidate and goes first
    t1.server.flush()
    assert not t1.server.busy()
    reg.get("t4")
    assert "t1" not in reg and reg.evictions == 1
    assert "t2" in reg and "t3" in reg and "t4" in reg


def test_evicted_tenant_reattaches_with_fresh_generation():
    reg = TenantRegistry("hera-80", capacity=2, window=4, engine="jax")
    g0 = reg.get("a").generation
    assert reg.evict("a") is True
    assert reg.get("a").generation == g0 + 1


# ---------------------------------------------------------------------------
# Rotation under concurrency: the (nonce, counter) uniqueness invariant
# ---------------------------------------------------------------------------
def test_rotation_under_concurrent_submits_no_pair_reuse():
    """Submitter threads hammer one session while another thread live-
    rotates it: across every served response, no (nonce, counter) pair
    may repeat, and every response must be bit-exact with a single-stream
    Cipher keyed by the nonce its counters were reserved under — i.e. a
    rotation never re-keys lanes buffered before it."""
    reg = TenantRegistry("hera-80", capacity=2, window=4, engine="jax",
                         seed=7)
    tenant = reg.get("spinner")
    srv = tenant.server
    sess = srv.open_session()
    entries, stop = [], threading.Event()
    elock = threading.Lock()

    def submitter(seed):
        rng = np.random.default_rng(seed)
        for _ in range(12):
            e = srv.submit_entry(HHERequest(
                session_id=sess.index, op="keystream",
                blocks=int(rng.integers(1, 4))))
            with elock:
                entries.append(e)
            time.sleep(0.001)

    def rotator():
        while not stop.is_set():
            time.sleep(0.01)
            reg.rotate_session("spinner", sess.index)

    threads = [threading.Thread(target=submitter, args=(50 + i,))
               for i in range(3)]
    rot = threading.Thread(target=rotator)
    rot.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rot.join()
    responses = {r.seq: r for r in srv.flush()}
    assert len(responses) == len(entries) == 36

    seen = set()
    for e in entries:
        for c in e.ctrs:
            pair = (e.nonce, int(c))
            assert pair not in seen, "keystream (nonce, counter) reuse"
            seen.add(pair)
        # bit-exact under the nonce recorded at submit time
        want = np.asarray(Cipher(
            tenant.batch.params, tenant.batch.key,
            np.frombuffer(e.nonce, np.uint8)
        ).keystream(jnp.asarray(e.ctrs, jnp.uint32)))
        np.testing.assert_array_equal(responses[e.seq].result, want)
    # the rotator actually rotated mid-traffic
    assert len({e.nonce for e in entries}) > 1


# ---------------------------------------------------------------------------
# Served-bytes parity: event-driven scheduler vs direct CipherBatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", KINDS)
def test_served_ciphertext_parity_with_direct_batch(name):
    """Ciphertext served through the event-driven loop (ragged submits,
    fill fires, a deadline fire on the tail) equals a direct CipherBatch
    keystream carve of the same lanes — for every cipher kind."""
    cb = CipherBatch(name, seed=21)
    srv = HHEServer(cb, window=4, engine="jax", deadline_s=10.0)
    s0, s1 = srv.open_session(), srv.open_session()
    rng = np.random.default_rng(9)
    l = cb.params.l
    toks = [rng.integers(0, cb.params.mod.q, size=(b, l), dtype=np.uint32)
            for b in (3, 5, 2)]
    for t, sid in zip(toks, (s0, s1, s0)):
        srv.submit(HHERequest(session_id=sid.index, op="encrypt_tokens",
                              payload=t))
    # tail lanes land via the deadline edge, not flush
    resp = srv.service(now=time.perf_counter() + 60.0)
    assert len(resp) == 3 and srv.latency_stats()["deadline_fires"] == 1

    # direct path: a second CipherBatch, same key, sessions pinned to the
    # SAME nonces — its batched keystream is the independent oracle
    direct = CipherBatch(cb.params, key=np.asarray(cb.key))
    for sess in cb.sessions:
        direct.add_session(nonce=sess.nonce)
    sids = np.concatenate([np.full(t.shape[0], sid.index)
                           for t, sid in zip(toks, (s0, s1, s0))])
    ctrs = np.concatenate([r.block_ctrs for r in resp])
    z = np.asarray(direct.keystream(sids, ctrs))
    want = np.asarray(cb.params.mod.add(
        jnp.asarray(np.concatenate(toks)), jnp.asarray(z)))
    got = np.concatenate([r.result for r in resp])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# TCP front end
# ---------------------------------------------------------------------------
def test_socket_round_trip_both_codecs():
    """Two clients on one plane — one JSON, one auto (msgpack when
    importable) — both directions exact, plus a live rotation and
    scheduler stats over the wire."""
    from repro.serve.server import CODEC_JSON, ServeClient, ServePlane

    async def main():
        reg = TenantRegistry("hera-80", capacity=2, window=4,
                             engine="jax", deadline_s=0.01)
        plane = ServePlane(reg, port=0, tick_s=0.002)
        host, port = await plane.start()
        cj = ServeClient(host, port, "json-tenant", codec=CODEC_JSON)
        cm = ServeClient(host, port, "auto-tenant")
        try:
            await cj.connect()
            await cm.connect()
            rng = np.random.default_rng(11)
            q, l = cj.params.mod.q, cj.params.l
            for c in (cj, cm):
                s = await c.open_session()
                toks = rng.integers(0, q, (3, l), dtype=np.uint32)
                r = await c.encrypt_to_server(s, toks)
                assert r["ok"], r
                np.testing.assert_array_equal(
                    np.asarray(r["result"], np.uint32), toks)
                await c.rotate(s)           # live rotation over the wire
                toks = rng.integers(0, q, (2, l), dtype=np.uint32)
                r, back = await c.decrypt_from_server(s, toks)
                assert r["ok"], r
                np.testing.assert_array_equal(back, toks)
            stats = await cj.stats()
            assert stats["count"] >= 2
            ping = await cm.call({"op": "ping"})
            assert ping["pong"] is True
            # tenant isolation visible at the wire level
            assert not np.array_equal(cj.key, cm.key)
        finally:
            await cj.close()
            await cm.close()
            await plane.stop()

    asyncio.run(main())


def test_socket_error_paths():
    """Wire errors come back as replies, never dropped connections."""
    from repro.serve.server import ServeClient, ServePlane

    async def main():
        reg = TenantRegistry("hera-80", capacity=2, window=4, engine="jax")
        plane = ServePlane(reg, port=0)
        host, port = await plane.start()
        c = ServeClient(host, port, "t")
        try:
            await c.connect()
            r = await c.call({"op": "nope"})
            assert not r["ok"] and "unknown op" in r["error"]
            r = await c.call({"op": "submit", "tenant": "t", "session": 99,
                              "hhe_op": "keystream", "blocks": 1})
            assert not r["ok"] and "unknown session" in r["error"]
            r = await c.call({"op": "hello", "tenant": "t",
                              "cipher": "rubato-128l"})
            assert not r["ok"] and "serves" in r["error"]
            # the connection survived all three errors
            assert (await c.call({"op": "ping"}))["pong"] is True
        finally:
            await c.close()
            await plane.stop()

    asyncio.run(main())
