"""`fast_read` on an erasure pool (pg_pool_t FLAG_EC_FAST_READ; upstream
doc/rados/operations/pools.rst): a client read asks every live shard at
once and answers from the first k chunks of one version, whichever
positions they are; and the two `osd_debug_inject_dispatch_delay_*`
options that make stragglers for it to pass.

The system is held to `benchmarks/reference_fastread.py` for EVERY
survivor set of k out of k+m, at 4+2 and 8+3: one live cluster a
profile serves them all in a module fixture (the replies of the shards
outside the set are kept back until the read has answered), and each
set is a case of its own.
"""
from __future__ import annotations

import asyncio
import dataclasses
import itertools
import json
import random
import time

import numpy as np
import pytest

from benchmarks import harness, reference, reference_fastread
from ceph_tpu.crush.osdmap import Incremental, OSDMap, Pool, pool_options
from ceph_tpu.mon import MonMap, Monitor
from ceph_tpu.msg.messages import MOSDECSubOpReadReply
from ceph_tpu.osd.ec_backend import ECBackend
from ceph_tpu.qa import faultinject
from ceph_tpu.rados import RadosError
from ceph_tpu.tools.cluster_boot import ephemeral_cluster
from ceph_tpu.utils.config import Config, ConfigError

from tests.test_cluster import ClusterHarness, free_ports, run

CHUNK = 4096
SIZE = 65536


def _sets(k, m):
    return list(itertools.combinations(range(k + m), k))


def _payload(seed: int, size: int = SIZE) -> bytes:
    return np.random.default_rng([seed, 35]).bytes(size)


async def _ec_pool(client, k, m, plugin="tpu", pg_num=1, name="fast"):
    await client.command({"prefix": "osd erasure-code-profile set",
                          "name": "prof", "profile": {
                              "plugin": plugin, "k": str(k), "m": str(m),
                              "technique": "reed_sol_van"}})
    await client.pool_create(name, pg_num=pg_num, pool_type="erasure",
                             erasure_code_profile="prof")
    return client.ioctx(name)


async def _set_fast(client, osds, pool, on=True):
    out = await client.command({"prefix": "osd pool set", "pool": pool,
                                "var": "fast_read", "val": int(on)})
    for _ in range(200):
        if all(o.osdmap.get_pool(pool).fast_read == on for o in osds):
            return out
        await asyncio.sleep(0.02)
    raise TimeoutError("the flag never reached every OSD's map")


def _primary(osds, pool="fast"):
    for osd in osds:
        for pg in osd.pgs.values():
            if pg.pool.name == pool and pg.is_primary():
                return osd, pg
    raise AssertionError("no primary")


class _Gate:
    """Hands a primary's backend the sub-read replies of the shards in
    `first`, in that order, and keeps everybody else's back until
    `open()`: the read meets exactly the arrivals the test chose."""

    def __init__(self, backend):
        self.backend = backend
        self.real = backend.handle_sub_op_reply
        self.first: list | None = None
        self.kept: dict = {}
        self.rewrite = None
        backend.handle_sub_op_reply = self

    def __call__(self, msg):
        if not isinstance(msg, MOSDECSubOpReadReply) or self.first is None:
            return self.real(msg)
        if self.rewrite is not None:
            self.rewrite(msg.payload)
        self.kept[msg.payload.get("shard")] = msg
        while self.first and self.first[0] in self.kept:
            self.real(self.kept.pop(self.first.pop(0)))

    def open(self) -> int:
        kept, self.kept, self.first = self.kept, {}, None
        for msg in kept.values():
            self.real(msg)
        return len(kept)

    def close(self):
        self.backend.handle_sub_op_reply = self.real


async def _serve_every_set(k: int, m: int) -> dict:
    """One cluster, one object, every survivor set: what the system
    answered from and with, beside what it was given."""
    import jax

    compiles: list[str] = []

    def on_compile(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    out: dict = {"sets": {}}
    async with ephemeral_cluster(k + m, prefix="fastread-") \
            as (client, osds, _mon):
        io = await _ec_pool(client, k, m)
        await _set_fast(client, osds, "fast")
        value = _payload(k * 100 + m)
        await io.write_full("obj", value)
        assert await io.read("obj") == value
        osd, pg = _primary(osds)
        backend = pg.backend
        mine = pg.acting.index(osd.whoami)
        shards = reference.expected_shards(value, k, m, CHUNK)
        # the encode program of this shape, on every device the offload
        # service may route a bucket to (the tests' backend has eight),
        # as the benchmark's warm-up does; every decode runs that one
        harness._encode_direct(
            {"plugin": "tpu", "k": str(k), "m": str(m),
             "technique": "reed_sol_van"}, jax.local_devices(), k, CHUNK,
            shards.shape[1] // CHUNK, range(1, 2))
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        gate = _Gate(backend)
        local = backend._verified_local_extent
        seen: dict = {}
        gather = backend._gather_chunks

        async def spy(*a, **kw):
            got, size, meta = await gather(*a, **kw)
            seen.update(used=sorted(got), asked=meta["asked"],
                        late=meta["late"], fast=kw.get("fast"))
            return got, size, meta
        backend._gather_chunks = spy
        try:
            for survivors in _sets(k, m):
                gate.first = [j for j in survivors if j != mine]
                backend._verified_local_extent = local if mine in survivors \
                    else (lambda *a, **kw: None)
                late0 = osd.perf.dump()["ec_subread_late"]
                bytes0 = osd.perf.dump()["ec_subread_late_bytes"]
                t0 = time.perf_counter()
                got = await io.read("obj")
                took = time.perf_counter() - t0
                kept = len(gate.kept)       # still out when it answered
                released = gate.open()
                await asyncio.sleep(0)
                out["sets"][survivors] = dict(
                    seen, got=got, took=took, kept=kept, released=released,
                    late_counted=osd.perf.dump()["ec_subread_late"] - late0,
                    late_bytes=osd.perf.dump()["ec_subread_late_bytes"]
                    - bytes0)
        finally:
            gate.close()
            backend._gather_chunks = gather
            backend._verified_local_extent = local
            jax.monitoring.unregister_event_duration_listener(on_compile)
        out.update(value=value, shards=shards, mine=mine,
                   compiles=len(compiles))
    return out


@pytest.fixture(scope="module")
def served_42():
    return run(_serve_every_set(4, 2), timeout=300)


@pytest.fixture(scope="module")
def served_83():
    return run(_serve_every_set(8, 3), timeout=600)


def _check_set(served, k, m, survivors):
    rec = served["sets"][survivors]
    value, shards, mine = served["value"], served["shards"], served["mine"]
    # the arrivals the system met: its own chunk first where it has one,
    # then the survivors' replies, then (too late) everybody else's
    # (where the set leaves the primary's position out, its own chunk
    # is unreadable and it has none)
    order = ([mine] if mine in survivors else []) \
        + [j for j in survivors if j != mine] \
        + [j for j in range(k + m) if j not in survivors and j != mine]
    arrivals = [(j, (1, 1), shards[j].tobytes()) for j in order]
    assert reference_fastread.may_answer(arrivals, k)
    want = reference_fastread.answer(arrivals, k, m, CHUNK)
    assert rec["fast"] is True
    assert rec["used"] == want["used"] == sorted(survivors)
    assert rec["got"] == want["data"][:len(value)] == value
    # it answered at the k-th chunk, the others still out, and those
    # were dropped when they came, and counted
    remote_survivors = len([j for j in survivors if j != mine])
    assert rec["asked"] == k + m - 1
    assert rec["late"] == want["late"] == rec["asked"] - remote_survivors
    assert rec["kept"] == rec["released"] == rec["late"]
    assert rec["late_counted"] == rec["late"]
    assert rec["late_bytes"] == rec["late"] * shards.shape[1]
    assert want["r"] == len(set(range(k)) - set(survivors))


@pytest.mark.parametrize("survivors", _sets(4, 2),
                         ids=lambda s: "-".join(map(str, s)))
def test_every_survivor_set_at_4_2_reads_as_the_reference(served_42,
                                                          survivors):
    _check_set(served_42, 4, 2, survivors)


@pytest.mark.parametrize("survivors", _sets(8, 3),
                         ids=lambda s: "-".join(map(str, s)))
def test_every_survivor_set_at_8_3_reads_as_the_reference(served_83,
                                                          survivors):
    _check_set(served_83, 8, 3, survivors)


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
def test_no_pattern_compiles_anything_after_the_encode_of_its_shape(
        k, m, served_42, served_83):
    """The recovery matrix is an argument of the program that encoded
    the shape: 15 + 165 survivor sets, r = 0..m, not one compile."""
    served = served_42 if k == 4 else served_83
    assert len(served["sets"]) == len(_sets(k, m))
    assert {len(set(range(k)) - set(s)) for s in served["sets"]} == \
        set(range(m + 1))
    assert served["compiles"] == 0


# -- versions -------------------------------------------------------------------------

async def _versions_case(newer: set, first: tuple, k=4, m=2):
    """A read that meets its own chunk (position 0) and then the
    replies of `first`, in that order, where the shards in `newer` say
    they hold a newer write."""
    async with ephemeral_cluster(k + m, prefix="fastver-") \
            as (client, osds, _mon):
        io = await _ec_pool(client, k, m)
        await _set_fast(client, osds, "fast")
        value = _payload(9)
        await io.write_full("obj", value)
        osd, pg = _primary(osds)
        gate = _Gate(pg.backend)

        def rewrite(payload):
            if payload.get("shard") in newer and "version" in payload:
                payload["version"] = [payload["version"][0],
                                      payload["version"][1] + 1]
        gate.rewrite = rewrite
        gate.first = list(first)
        try:
            try:
                got = await io.read("obj")
            except RadosError as e:
                got = e
        finally:
            gate.open()
            gate.close()
        return got, value, pg.acting.index(osd.whoami)


@pytest.mark.parametrize("newer,first,answers", [
    # k chunks of the acked version come first; the newer one, short of
    # k, is still out: nothing newer was seen, the read answers
    ({4, 5}, (1, 2, 3), True),
    ({4}, (1, 2, 3, 4), True),
    # a newer chunk seen before the acked version has its k: EIO, never
    # the older version, however many of its chunks are there
    ({4}, (1, 2, 4, 3), False),
    ({5}, (5, 1, 2, 3), False),
    # two versions, neither with k chunks: nothing to answer from
    ({1, 4, 5}, (1, 2, 3, 4, 5), False),
])
def test_versions_never_mix_and_a_newer_one_short_of_k_is_eio(
        newer, first, answers):
    got, value, mine = run(_versions_case(newer, first))
    assert mine == 0
    arrivals = [(j, 2 if j in newer else 1, b"") for j in (mine, *first)]
    assert reference_fastread.may_answer(arrivals, 4) is answers
    if answers:
        assert got == value
    else:
        assert isinstance(got, RadosError) and got.rc == -5


def test_reference_picks_the_first_k_of_one_version():
    k, m = 4, 2
    value = _payload(3, 2 * k * CHUNK)
    shards = reference.expected_shards(value, k, m, CHUNK)
    old = reference.expected_shards(_payload(4, 2 * k * CHUNK), k, m, CHUNK)
    a = [(0, 2, shards[0].tobytes()), (5, 1, old[5].tobytes()),
         (4, 2, shards[4].tobytes()), (2, 2, shards[2].tobytes()),
         (1, 1, old[1].tobytes()), (5, 2, shards[5].tobytes()),
         (3, 2, shards[3].tobytes())]
    ans = reference_fastread.answer(a, k, m, CHUNK)
    assert ans["used"] == [0, 2, 4, 5] and ans["want"] == [1, 3]
    assert ans["r"] == 2 and ans["late"] == 1 and ans["data"] == value
    assert reference_fastread.may_answer(a, k)
    # the older version reaching k first, a newer chunk seen: EIO
    b = [(j, 1, old[j].tobytes()) for j in (0, 1, 2)] \
        + [(4, 2, shards[4].tobytes()), (3, 1, old[3].tobytes())]
    assert not reference_fastread.may_answer(b, k)
    assert not reference_fastread.may_answer(a[:4], k)     # short of k
    with pytest.raises(ValueError):
        reference_fastread.answer(a[:4], k, m, CHUNK)
    assert reference_fastread.expected_decode_share(0.1, 8) == \
        pytest.approx(1 - 0.9 ** 7)
    assert reference_fastread.expected_decode_share(0.0, 8) == 0.0
    assert reference_fastread.expected_decode_share(0.1, 8, 2) == \
        pytest.approx(0.19)


# -- a straggler --------------------------------------------------------------------------

async def _read_past_a_straggler(fast: bool):
    k, m, hold = 4, 2, 0.4
    async with ephemeral_cluster(k + m, prefix="faststrag-") \
            as (client, osds, _mon):
        io = await _ec_pool(client, k, m)
        await _set_fast(client, osds, "fast", on=fast)
        value = _payload(5)
        await io.write_full("obj", value)
        osd, pg = _primary(osds)
        # the OSD of a data position that is not the primary's holds
        # every sub-op back
        slow = next(o for o in osds
                    if o.whoami == pg.acting[1] and o is not osd)
        slow.config.set("osd_debug_inject_dispatch_delay_duration", hold)
        slow.config.set("osd_debug_inject_dispatch_delay_probability", 1.0)
        t0 = time.perf_counter()
        got = await io.read("obj")
        took = time.perf_counter() - t0
        assert got == value
        held = slow.perf.dump()["dispatch_delays"]
        slow.config.set("osd_debug_inject_dispatch_delay_probability", 0.0)
        await asyncio.sleep(hold + 0.1)     # the held sub-read ends
        return took, hold, held, osd.perf.dump()["ec_subread_late"]


def test_a_held_data_shard_delays_a_plain_read_and_not_a_fast_one():
    took, hold, held, late = run(_read_past_a_straggler(fast=True))
    assert held == 1 and took < hold / 2
    assert late == 2        # the straggler's reply and one parity's
    took, hold, held, late = run(_read_past_a_straggler(fast=False))
    assert held == 1 and took >= hold
    assert late == 0


# -- who reads fast --------------------------------------------------------------------------

def test_recovery_scrub_and_stat_never_read_fast(monkeypatch):
    calls: list = []
    real = ECBackend._gather_chunks

    async def spy(self, oid, *a, **kw):
        calls.append((kw.get("fast", False), kw.get("chunk_len")))
        return await real(self, oid, *a, **kw)
    monkeypatch.setattr(ECBackend, "_gather_chunks", spy)

    async def body():
        async with ephemeral_cluster(6, prefix="fastwho-") \
                as (client, osds, _mon):
            io = await _ec_pool(client, 4, 2)
            await _set_fast(client, osds, "fast")
            value = _payload(6)
            await io.write_full("obj", value)
            osd, pg = _primary(osds)
            calls.clear()
            assert await io.read("obj") == value
            assert [c[0] for c in calls] == [True]
            calls.clear()
            # a stat and an existence check that cannot be answered
            # from the primary's own chunk, a reconstruction for
            # recovery, an RMW's read of the stripe, a deep scrub
            monkeypatch.setattr(pg.backend, "_verified_local_extent",
                                lambda *a, **kw: None)
            assert await pg.backend.execute_stat("obj") == len(value)
            assert await pg.backend.object_exists("obj")
            monkeypatch.undo()
            monkeypatch.setattr(ECBackend, "_gather_chunks", spy)
            chunk, _attrs = await pg.backend._reconstruct(
                "obj", 1, frozenset())
            assert len(chunk) == len(value) // 4
            await io.write("obj", b"x" * 10, offset=5)
            await pg.scrub(deep=True)
            # the stat, the reconstruction and the RMW gathered (in two
            # rounds); the scrub reads every shard where it lies
            assert calls == [(False, 0), (False, None), (False, 4096)]
            # and the flag off again: the client's read takes two rounds
            await _set_fast(client, osds, "fast", on=False)
            calls.clear()
            await io.read("obj")
            assert [c[0] for c in calls] == [False]
    run(body())


def test_an_osds_own_default_reads_fast_where_the_pools_flag_is_off():
    async def body():
        async with ephemeral_cluster(6, prefix="fastdef-") \
                as (client, osds, _mon):
            io = await _ec_pool(client, 4, 2)
            value = _payload(7)
            await io.write_full("obj", value)
            osd, pg = _primary(osds)
            assert not pg.pool.fast_read and not pg.backend._reads_fast()
            osd.config.set("osd_pool_default_ec_fast_read", True)
            assert pg.backend._reads_fast()
            assert await io.read("obj") == value
            assert osd.perf.dump()["ec_subread_late"] == 2
    run(body())


# -- the flag in the map, and the mon ------------------------------------------------------

def test_flag_survives_the_maps_encode_and_decode():
    m = OSDMap()
    m.create_pool("ec", type="erasure", size=6, min_size=5, fast_read=True)
    m.create_pool("rep")
    def decoded(d):
        back = OSDMap()
        back.load_dict(json.loads(json.dumps(d)))
        return back
    back = decoded(m.to_dict())
    assert back.get_pool("ec").fast_read is True
    assert back.get_pool("rep").fast_read is False
    assert Pool(id=9, name="old").fast_read is False
    # a map written before the flag existed reads as off
    d = m.to_dict()
    for pool in d["pools"].values():
        pool.pop("fast_read")
    assert decoded(d).get_pool("ec").fast_read is False
    ec = m.get_pool("ec")
    inc = Incremental(epoch=m.epoch + 1, new_pools={
        ec.id: dataclasses.replace(ec, fast_read=False)})
    again = Incremental.from_dict(json.loads(json.dumps(inc.to_dict())))
    assert again.new_pools[ec.id].fast_read is False
    back.apply_incremental(again)
    assert back.get_pool("ec").fast_read is False


def test_pool_set_fast_read_its_default_and_a_mon_restart(tmp_path):
    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        await c.start()
        try:
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "prof", "profile": {
                                  "plugin": "jerasure", "k": "2",
                                  "m": "1"}})
            await cl.pool_create("ec", pg_num=2, pool_type="erasure",
                                 erasure_code_profile="prof")
            await cl.pool_create("rep", pg_num=2)
            mon = next(iter(c.mons.values()))
            assert not mon.osdmon.osdmap.get_pool("ec").fast_read
            out = await cl.command({"prefix": "osd pool set", "pool": "ec",
                                    "var": "fast_read", "val": "1"})
            assert out["fast_read"] is True
            assert mon.osdmon.osdmap.get_pool("ec").fast_read
            assert mon.osdmon.osdmap.epoch >= out["epoch"]
            # refused: a replicated pool, an unknown variable, a value
            # that is neither 0 nor 1, a pool that is not there
            for bad in ({"pool": "rep", "var": "fast_read", "val": "1"},
                        {"pool": "ec", "var": "size", "val": "3"},
                        {"pool": "ec", "var": "fast_read", "val": "maybe"},
                        {"pool": "nope", "var": "fast_read", "val": "1"}):
                with pytest.raises(RuntimeError, match="ValueError"):
                    await cl.command({"prefix": "osd pool set", **bad})
            assert not mon.osdmon.osdmap.get_pool("rep").fast_read
            # the mon's default, where the pool is created
            mon.config.set("osd_pool_default_ec_fast_read", True)
            await cl.pool_create("ec2", pg_num=2, pool_type="erasure",
                                 erasure_code_profile="prof")
            await cl.pool_create("rep2", pg_num=2)
            assert mon.osdmon.osdmap.get_pool("ec2").fast_read
            assert not mon.osdmon.osdmap.get_pool("rep2").fast_read
            await cl.command({"prefix": "osd pool set", "pool": "ec2",
                              "var": "fast_read", "val": 0})
            assert not mon.osdmon.osdmap.get_pool("ec2").fast_read
            # a mon restart reads the flag back from its store
            name = mon.name
            await mon.stop()
            again = Monitor(name, c.monmap,
                            store_path=str(tmp_path / f"mon.{name}"))
            c.mons[name] = again
            await again.start()
            for _ in range(200):
                if again.osdmon.osdmap.pool_names.get("ec"):
                    break
                await asyncio.sleep(0.05)
            assert again.osdmon.osdmap.get_pool("ec").fast_read
            assert not again.osdmon.osdmap.get_pool("ec2").fast_read
        finally:
            await c.stop()
    run(body())


# -- the injector ------------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_injector_holds_the_stated_share_and_repeats_for_a_seed(seed):
    def draws(s):
        inj = faultinject.FaultInjector(seed=s)
        return [inj.hold_dispatch(0.1, "osd.0 op") for _ in range(1000)]
    first = draws(seed)
    # the n-th draw is a pure function of (seed, site, n)
    want = [random.Random(f"{seed}:dispatch_delay:{n}").random() < 0.1
            for n in range(1000)]
    assert first == want and first == draws(seed)
    assert first != draws(seed + 1)
    assert 70 <= sum(first) <= 130
    inj = faultinject.FaultInjector(seed=seed)
    assert not any(inj.hold_dispatch(0.0, "x") for _ in range(100))
    assert all(inj.hold_dispatch(1.0, "x") for _ in range(100))
    assert [e[0] for e in inj.log] == ["dispatch_delay"] * 100


def test_only_ops_and_sub_op_requests_are_held_never_a_heartbeat(
        monkeypatch):
    from ceph_tpu.osd.daemon import OSD
    monkeypatch.setattr(OSD, "HB_INTERVAL", 0.1)

    async def body():
        k, m = 2, 1
        async with ephemeral_cluster(k + m, prefix="fasthold-") \
                as (client, osds, _mon):
            io = await _ec_pool(client, k, m)
            for osd in osds:
                osd.config.set(
                    "osd_debug_inject_dispatch_delay_duration", 0.05)
                osd.config.set(
                    "osd_debug_inject_dispatch_delay_probability", 1.0)

            def held():
                return sum(o.perf.dump()["dispatch_delays"] for o in osds)
            # pings, their replies and map traffic flow: nothing is held
            await asyncio.sleep(0.6)
            assert held() == 0
            t0 = time.perf_counter()
            await io.write_full("obj", b"v" * 8192)
            # the client's op and a sub-write on each of the two peers;
            # the write waits for the op's hold and then the sub-ops'
            assert held() == 3
            assert time.perf_counter() - t0 >= 0.1
            assert await io.read("obj") == b"v" * 8192
            assert held() == 3 + 1 + 1      # the op, one sub-read (k=2)
            for osd in osds:
                osd.config.set(
                    "osd_debug_inject_dispatch_delay_probability", 0.0)
                assert osd.op_queue.hold is None
            await io.read("obj")
            assert held() == 5
    run(body())


def test_a_hold_keeps_the_order_of_a_pgs_sub_writes():
    """Writes through a peer that holds every other sub-write: each is
    handed to the backend in the order it came off the wire, held or
    not, and the object ends as the last write left it."""
    async def body():
        k, m = 2, 1
        async with ephemeral_cluster(k + m, prefix="fastorder-") \
                as (client, osds, _mon):
            io = await _ec_pool(client, k, m)
            await io.write_full("obj", b"\0" * 8192)
            osd, pg = _primary(osds)
            peer = next(o for o in osds if o is not osd)
            came: list = []
            applied: list = []
            backend = peer.pgs[pg.pgid].backend
            real_hold, real_apply = peer._hold_sub_op, backend.handle_sub_op

            def arrive(pg_, conn, msg):
                came.append(msg.payload["tid"])
                return real_hold(pg_, conn, msg)

            async def apply(conn, msg):
                applied.append(msg.payload["tid"])
                return await real_apply(conn, msg)
            peer._hold_sub_op, backend.handle_sub_op = arrive, apply
            flips = itertools.cycle([True, False])
            peer._dispatch_hold = lambda kind: (
                peer._held(kind) if next(flips) else None)
            peer.config.set("osd_debug_inject_dispatch_delay_duration", 0.05)
            peer.config.set(
                "osd_debug_inject_dispatch_delay_probability", 0.5)
            await asyncio.gather(*[
                io.write_full(f"o{i}", bytes([i]) * 8192) for i in range(8)])
            for i in range(6):
                await io.write_full("obj", bytes([65 + i]) * 8192)
            assert len(came) == 14 and applied == came
            assert peer.perf.dump()["dispatch_delays"] == 7
            assert not peer._behind_hold
            assert await io.read("obj") == b"F" * 8192
            for i in range(8):
                assert await io.read(f"o{i}") == bytes([i]) * 8192
    run(body())


def test_the_three_options_are_declared_with_their_upstream_meaning():
    from ceph_tpu.osd.daemon import OSD
    osd = OSD(0, [("127.0.0.1", 1)])
    schema = osd.config.schema()
    p = schema["osd_debug_inject_dispatch_delay_probability"]
    d = schema["osd_debug_inject_dispatch_delay_duration"]
    f = schema["osd_pool_default_ec_fast_read"]
    assert (p.type, p.default, d.type, d.default, f.type, f.default) == \
        ("float", 0.0, "float", 0.0, "bool", False)
    assert "dequeue_op" in p.description and "heartbeat" in p.description
    assert "Departure" in f.description
    assert osd.op_queue.hold is None
    with pytest.raises(ConfigError):
        osd.config.set("osd_debug_inject_dispatch_delay_probability", 1.5)
    with pytest.raises(ConfigError):
        osd.config.set("osd_debug_inject_dispatch_delay_duration", -1)
    assert Config(pool_options()).get(
        "osd_pool_default_ec_fast_read") is False
    port = free_ports(1)[0]
    mon = Monitor("m0", MonMap({"m0": ("127.0.0.1", port)}))
    assert mon.config.get("osd_pool_default_ec_fast_read") is False
