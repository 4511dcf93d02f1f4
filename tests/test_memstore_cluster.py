"""MemStore by reference under a small EC cluster (k=2 m=1 on three
OSDs, and the north star's k=8 m=3 on eleven): shards are kept as the
buffers they arrived in and served as windows on them, and nothing a
client can see changes: reads beside overwrites are whole, a rotted
shard is caught by the read's crc gate and by a deep scrub, the stores
hold what the shards add up to; after overwrites, partial writes,
repair, recovery, snapshots and rollbacks what a client reads is what a
dictionary holds (`qa/rados_model.py`) and what the stores hold is what
`benchmarks/reference.py` (an independent Reed-Solomon) says each shard
of that value is, byte for byte."""
from __future__ import annotations

import asyncio
import os
import random

import pytest

from benchmarks import reference
from ceph_tpu.qa import ModelRunner
from ceph_tpu.utils import copytrack

from tests.test_cluster import fast_timers, run  # noqa: F401
from tests.test_ec_rmw import make_ec_cluster

CHUNK = 4096
K, M = 2, 1


def _shard_len(size: int) -> int:
    stripes = -(-size // (K * CHUNK))
    return stripes * CHUNK


def _holders(c, oid):
    """(osd, pg) of every OSD holding a shard of `oid`, primary first."""
    out = [(osd, pg) for osd in c.osds.values() for pg in osd.pgs.values()
           if pg.pool.name == "ecpool" and pg.backend.local_exists(oid)]
    return sorted(out, key=lambda op: not op[1].is_primary())


def _stored(osd, pg, oid):
    return osd.store._colls[pg.backend.coll()][pg.backend.ghobject(oid)]


def _ledger(stage):
    return dict(copytrack.snapshot()["stages"][stage])


def test_shards_are_kept_and_served_by_reference(tmp_path):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, K, M, 3, pg_num=4)
        try:
            for osd in c.osds.values():
                osd.store.USED_BYTES_TTL = 0.0
            sizes = {"big": 1 << 20, "odd": 3 * 8192 + 100, "one": 1,
                     "stripe": K * CHUNK}
            payloads = {n: os.urandom(s) for n, s in sizes.items()}
            w0, r0 = _ledger("store_write"), _ledger("store_read")
            for name, data in payloads.items():
                await io.write_full(name, data)
            for name, data in payloads.items():
                assert await io.read(name) == data
            # every shard of every object is the buffer it arrived in
            # (a peer's: a read-only window on an rx body; the
            # primary's own: the bytes `Transaction.write` snapshotted)
            for name in payloads:
                holders = _holders(c, name)
                assert len(holders) == K + M
                for osd, pg in holders:
                    kept = _stored(osd, pg, name).data
                    assert not isinstance(kept, bytearray), (name, osd.whoami)
                    assert len(kept) == _shard_len(sizes[name])
                    got = osd.store.read(pg.backend.coll(),
                                         pg.backend.ghobject(name))
                    assert type(got) is memoryview and got.readonly
            w1, r1 = _ledger("store_write"), _ledger("store_read")
            shard_bytes = sum((K + M) * _shard_len(s) for s in sizes.values())
            # all of them adopted; of the primaries' own, those that
            # were a writable plane of the encode's output were copied
            # on the way, once, by `Transaction.write`
            assert w1["referenced_bytes"] - w0["referenced_bytes"] \
                == shard_bytes
            assert _shard_len(sizes["big"]) \
                <= w1["copied_bytes"] - w0["copied_bytes"] \
                <= shard_bytes // (K + M)
            assert r1["referenced_bytes"] > r0["referenced_bytes"]
            assert r1["copied_bytes"] == r0["copied_bytes"]
            # what `store_bytes_per_user_byte` divides: the stores hold
            # the shards' lengths and nothing beside them
            assert sum(o.store.used_bytes() for o in c.osds.values()) \
                == shard_bytes
        finally:
            await c.stop()
    run(body())


def test_reads_beside_overwrites_are_whole_old_or_whole_new(tmp_path):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, K, M, 3)
        try:
            size = 1 << 20          # 512 KiB shards: sent by reference
            versions = [os.urandom(size) for _ in range(4)]
            await io.write_full("o", versions[0])
            # a shard as a reply carries it, held while the object is
            # replaced under it
            osd, pg = _holders(c, "o")[1]
            held = pg.backend._verified_local_extent("o", 0, -1)[0]
            assert type(held) is memoryview
            shard_then = bytes(held)

            reads = []

            async def read():
                reads.append(bytes(await io.read("o")))

            await asyncio.gather(
                read(), io.write_full("o", versions[1]), read(), read(),
                io.write_full("o", versions[2]), read(),
                io.write_full("o", versions[3]), read(), read())
            assert len(reads) == 6
            for got in reads:
                assert got in versions, "torn read"
            assert await io.read("o") == versions[3]
            assert held == shard_then
            now = osd.store.read(pg.backend.coll(),
                                 pg.backend.ghobject("o"))
            assert now != shard_then
        finally:
            await c.stop()
    run(body())


def test_rot_in_an_adopted_shard_is_caught_by_read_and_by_scrub(tmp_path):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, K, M, 3)
        try:
            payload = os.urandom(3 * 8192 + 100)
            await io.write_full("obj", payload)
            (_p, prim_pg), (victim, vpg) = _holders(c, "obj")[:2]
            cid, gh = vpg.backend.coll(), vpg.backend.ghobject("obj")
            before = victim.store.read(cid, gh)
            good = bytes(before)
            assert not isinstance(_stored(victim, vpg, "obj").data, bytearray)
            assert victim.store.corrupt(cid, gh, 10)
            # the one-byte write made the shard private; the window
            # handed out before it still shows the good bytes
            assert isinstance(_stored(victim, vpg, "obj").data, bytearray)
            assert before == good
            assert victim.store.read(cid, gh) != good
            # the shard's crc gate refuses it, and the read goes round
            assert vpg.backend._verified_local_extent("obj", 0, -1) is None
            assert await io.read("obj") == payload
            # a deep scrub finds it and repairs it with no client read
            res = await prim_pg.scrub(deep=True)
            assert res["errors"] == 1 and res["repaired"] == 1, res
            await _wait(lambda: victim.store.read(cid, gh) == good,
                        "the repair's push never landed")
            # a pushed shard replaces the object: kept by reference again
            assert not isinstance(_stored(victim, vpg, "obj").data, bytearray)
            res = await prim_pg.scrub(deep=True)
            assert res["errors"] == 0, res
            assert await io.read("obj") == payload
        finally:
            await c.stop()
    run(body())


# -- the same at both shapes, against the references --------------------------

SHAPES = [(2, 1, 3), (8, 3, 11)]
IDS = ["k2m1", "k8m3"]


def _ref_delta(before):
    return tuple(_ledger(stage)["referenced_bytes"]
                 - before[stage]["referenced_bytes"]
                 for stage in ("store_write", "store_read"))


def _both():
    return {stage: _ledger(stage) for stage in ("store_write", "store_read")}


def _assert_shards_are_the_references(c, k, m, oid, value):
    """Every holder's blob is the reference's shard of `value` at the
    position its attrs name."""
    holders = _holders(c, oid)
    assert len(holders) == k + m, (oid, len(holders))
    chunk = holders[0][1].backend.sinfo.chunk_size
    want = reference.expected_shards(value, k, m, chunk)
    seen = set()
    for osd, pg in holders:
        cid, gh = pg.backend.coll(), pg.backend.ghobject(oid)
        pos = int(osd.store.getattrs(cid, gh)["shard"])
        seen.add(pos)
        got = osd.store.read(cid, gh)
        assert bytes(got) == want[pos].tobytes(), (oid, osd.whoami, pos)
    assert seen == set(range(k + m))


async def _wait(cond, what, timeout=30.0):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while not cond():
        assert loop.time() < deadline, what
        await asyncio.sleep(0.05)


@pytest.mark.parametrize("k,m,n_osds", SHAPES, ids=IDS)
def test_writes_overwrites_and_partial_writes_match_the_reference(
        tmp_path, k, m, n_osds):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds, pg_num=4)
        try:
            rng = random.Random(k)
            width = k * CHUNK
            before = _both()
            model = {}
            for name, size in [("full", 4 * width), ("odd", 3 * width + 100),
                               ("tiny", 7), ("big", 64 * width)]:
                model[name] = bytearray(rng.randbytes(size))
                await io.write_full(name, bytes(model[name]))
            # an overwrite replaces; the shards are the new value's
            model["full"] = bytearray(rng.randbytes(2 * width + 5))
            await io.write_full("full", bytes(model["full"]))
            # partial writes: inside one chunk, across a stripe's edge,
            # past the end, and an append
            for off, n in [(10, 50), (width - 30, 60), (5 * width, 900)]:
                piece = rng.randbytes(n)
                await io.write("odd", piece, offset=off)
                if len(model["odd"]) < off:
                    model["odd"].extend(bytes(off - len(model["odd"])))
                model["odd"][off:off + n] = piece
            piece = rng.randbytes(width + 3)
            await io.append("tiny", piece)
            model["tiny"] += piece
            for name, want in model.items():
                assert await io.read(name) == bytes(want), name
                assert await io.read(name, 5, 40) == bytes(want[5:45]), name
                _assert_shards_are_the_references(c, k, m, name, bytes(want))
            # a deep scrub of every PG agrees
            for osd in c.osds.values():
                for pg in osd.pgs.values():
                    if pg.pool.name == "ecpool" and pg.is_primary():
                        res = await pg.scrub(deep=True)
                        assert res["errors"] == 0, res
            wrote, read = _ref_delta(before)
            assert wrote > 0 and read > 0
            # the untouched objects are still the buffers they came in
            for osd, pg in _holders(c, "big"):
                assert not isinstance(_stored(osd, pg, "big").data, bytearray)
        finally:
            await c.stop()
    run(body(), timeout=120)


@pytest.mark.parametrize("k,m,n_osds", SHAPES, ids=IDS)
def test_a_rotted_byte_is_found_and_repaired_to_the_reference(
        tmp_path, k, m, n_osds):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds)
        try:
            value = random.Random(m).randbytes(5 * k * CHUNK + 11)
            await io.write_full("obj", value)
            holders = _holders(c, "obj")
            prim_pg = holders[0][1]
            victim, vpg = holders[-1]
            cid, gh = vpg.backend.coll(), vpg.backend.ghobject("obj")
            held = victim.store.read(cid, gh)
            good = bytes(held)
            before = _both()
            assert victim.store.corrupt(cid, gh, 4097, xor=0x40)
            assert held == good and victim.store.read(cid, gh) != good
            assert await io.read("obj") == value
            res = await prim_pg.scrub(deep=True)
            assert res["errors"] == 1 and res["repaired"] == 1, res
            await _wait(lambda: victim.store.read(cid, gh) == good,
                        "the repair's push never landed")
            _assert_shards_are_the_references(c, k, m, "obj", value)
            assert not isinstance(_stored(victim, vpg, "obj").data, bytearray)
            res = await prim_pg.scrub(deep=True)
            assert res["errors"] == 0, res
            wrote, read = _ref_delta(before)
            assert wrote > 0 and read > 0
        finally:
            await c.stop()
    run(body(), timeout=120)


# k=2 m=1 takes no write with a shard down (min_size is k + 1): m=2 here
@pytest.mark.parametrize("k,m,n_osds", [(2, 2, 4), (8, 3, 11)],
                         ids=["k2m2", "k8m3"])
def test_recovery_pushes_to_a_returning_osd_are_kept_by_reference(
        tmp_path, k, m, n_osds):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds, pg_num=2)
        try:
            rng = random.Random(n_osds)
            width = k * CHUNK
            model = {f"o{i}": rng.randbytes(width * (i + 1) + i)
                     for i in range(4)}
            for name, value in model.items():
                await io.write_full(name, value)
            victim = max(c.osds)
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            # written and overwritten while it is away
            model["o1"] = rng.randbytes(3 * width + 9)
            model["late"] = rng.randbytes(2 * width)
            await io.write_full("o1", model["o1"])
            await io.write_full("late", model["late"])
            for name, value in model.items():
                assert await io.read(name) == value, (name, "degraded")
            before = _both()
            await c.start_osd(victim, store=store)
            back = c.osds[victim]

            def caught_up():
                for name in ("o1", "late"):
                    mine = [(o, pg) for o, pg in _holders(c, name)
                            if o is back]
                    if len(_holders(c, name)) < k + m or not mine:
                        return False
                    cid = mine[0][1].backend.coll()
                    gh = mine[0][1].backend.ghobject(name)
                    attrs = back.store.getattrs(cid, gh)
                    chunk = mine[0][1].backend.sinfo.chunk_size
                    want = reference.expected_shards(
                        model[name], k, m, chunk)[int(attrs["shard"])]
                    if bytes(back.store.read(cid, gh)) != want.tobytes():
                        return False
                return True
            await _wait(caught_up, "recovery never pushed to the osd")
            for name, value in model.items():
                assert await io.read(name) == value, name
                _assert_shards_are_the_references(c, k, m, name, value)
            # what recovery pushed replaced the objects: kept as it came
            for name in ("o1", "late"):
                pg = next(pg for o, pg in _holders(c, name) if o is back)
                assert not isinstance(_stored(back, pg, name).data,
                                      bytearray), name
            wrote, read = _ref_delta(before)
            assert wrote > 0 and read > 0
        finally:
            await c.stop()
    run(body(), timeout=150)


@pytest.mark.parametrize("k,m,n_osds", SHAPES, ids=IDS)
def test_a_snapshots_clone_and_a_rollback_match_the_model(
        tmp_path, k, m, n_osds):
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds)
        try:
            rng = random.Random(k + m)
            width = k * CHUNK
            v1 = rng.randbytes(3 * width + 17)
            await io.write_full("s", v1)
            before = _both()
            snap = await io.selfmanaged_snap_create()
            io.set_snap_context(snap, [snap])
            # the first write under the snap clones every shard: an
            # adopted buffer is shared by head and clone, then the head
            # is replaced (write_full) or made private (partial write)
            v2 = bytearray(v1)
            v2[100:110] = b"0123456789"
            await io.write("s", b"0123456789", offset=100)
            assert await io.read("s") == bytes(v2)
            assert await io.read("s", snapid=snap) == v1
            v3 = rng.randbytes(width)
            await io.write_full("s", v3)
            assert await io.read("s") == v3
            assert await io.read("s", snapid=snap) == v1
            _assert_shards_are_the_references(c, k, m, "s", v3)
            await io.rollback("s", snap)
            assert await io.read("s") == v1
            assert await io.read("s", snapid=snap) == v1
            _assert_shards_are_the_references(c, k, m, "s", v1)
            # and the head diverges from the clone again
            await io.write("s", b"after", offset=width - 2)
            v4 = bytearray(v1)
            v4[width - 2:width + 3] = b"after"
            assert await io.read("s") == bytes(v4)
            assert await io.read("s", snapid=snap) == v1
            _assert_shards_are_the_references(c, k, m, "s", bytes(v4))
            wrote, read = _ref_delta(before)
            assert wrote > 0 and read > 0
        finally:
            await c.stop()
    run(body(), timeout=120)


@pytest.mark.parametrize("k,m,n_osds", SHAPES, ids=IDS)
def test_random_ops_with_snapshots_match_rados_model(tmp_path, k, m, n_osds):
    """`qa/rados_model.py`'s random mix (writes, appends, truncates,
    removes, xattrs, snapshots, rollbacks), no thrashing: every outcome
    is knowable and the final state is the model's exactly."""
    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, k, m, n_osds, pg_num=4)
        try:
            before = _both()
            runner = ModelRunner(io, random.Random(34 + k), ec_pool=True,
                                 stripe=k * CHUNK, max_objects=10,
                                 enable_snaps=True)
            for _ in range(60):
                await runner.step()
            await runner.final_check()
            assert runner.uncertain_ops == 0 and not runner.uncertain
            wrote, read = _ref_delta(before)
            assert wrote > 0 and read > 0
        finally:
            await c.stop()
    run(body(), timeout=150)
