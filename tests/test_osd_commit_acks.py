"""No acknowledgement leaves an OSD before the store's commit: a shard's
sub-op reply, a replica's, the primary's own shard and with it the
client's reply all wait for `on_commit`. On a store that commits inside
`queue_transaction` (MemStore) the order of events is what it was."""
from __future__ import annotations

import asyncio

import pytest

from ceph_tpu.msg import messenger
from ceph_tpu.objectstore.bluestore import BlueStore
from ceph_tpu.rados import RadosClient

from tests.test_bluestore_commit import Syncs, syncs  # noqa: F401
from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401

SUB_WRITES = {"MOSDECSubOpWrite", "MOSDRepOp"}
SUB_REPLIES = {"MOSDECSubOpWriteReply", "MOSDRepOpReply"}
WATCHED = SUB_WRITES | SUB_REPLIES | {"MOSDOpReply"}
POOLS = {"erasure": dict(pg_num=4, pool_type="erasure",
                         erasure_code_profile="p21"),
         "replicated": dict(pg_num=4, size=3)}


@pytest.fixture
def sent(monkeypatch):
    """The type of every watched message as a connection takes it; of a
    client's reply only one that says done (`rc` 0: a daemon that goes
    down tells the client to send again, which acknowledges nothing)."""
    log: list[str] = []
    real = messenger.Connection.send_message

    def send_message(conn, msg):
        name = type(msg).__name__
        if name in WATCHED and not (name == "MOSDOpReply"
                                    and msg.payload.get("rc", 0)):
            log.append(name)
        return real(conn, msg)
    monkeypatch.setattr(messenger.Connection, "send_message", send_message)
    return log


async def _cluster(tmp_path, kind: str, pool: str):
    factory = (lambda i: BlueStore(str(tmp_path / f"osd{i}"))) \
        if kind == "bluestore" else None
    c = ClusterHarness(tmp_path, store_factory=factory)
    await c.start()
    cl = await c.client()
    if pool == "erasure":
        await cl.command({"prefix": "osd erasure-code-profile set",
                          "name": "p21",
                          "profile": {"plugin": "jerasure", "k": "2",
                                      "m": "1"}})
    await cl.pool_create("p", **POOLS[pool])
    return c, cl, cl.ioctx("p")


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("kind", ["bluestore", "memstore"])
def test_no_ack_leaves_before_the_commit(tmp_path, syncs, sent, kind, pool):
    async def body():
        c, _cl, io = await _cluster(tmp_path, kind, pool)
        try:
            await io.write_full("warm", b"w" * 70_000)  # peered, all imported
            del sent[:]
            syncs.hold()
            value = b"v" * 70_000
            write = asyncio.create_task(io.write_full("x", value))
            if kind == "bluestore":
                # the three stores stand in their syncs: the sub-writes
                # have left the primary, nothing has come back
                await asyncio.sleep(0.6)
                assert not write.done()
                assert sorted(sent) == sorted(SUB_WRITES & set(sent)) * 2 \
                    and len(sent) == 2, sent
                # the queued write is readable on every shard meanwhile
                assert all(o.store._q.busy or o.store._q.queued
                           for o in c.osds.values())
            syncs.release()
            await asyncio.wait_for(write, 20)
            # two sub-writes out, their two replies back, then the
            # client's: on either store
            assert len(sent) == 5, sent
            assert set(sent[:2]) <= SUB_WRITES
            assert set(sent[2:4]) <= SUB_REPLIES
            assert sent[4] == "MOSDOpReply"
            assert await io.read("x") == value
            if kind == "bluestore":
                for o in c.osds.values():
                    st = o.store.stats()
                    assert st["acks_before_sync"] == 0 and st["txcs"] > 0
        finally:
            syncs.release()
            await c.stop()
    run(body())


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_the_primarys_own_shard_is_one_of_the_commits(tmp_path, syncs, sent,
                                                      pool):
    """The peers' stores commit, the primary's is held: the replies are
    in and the client still waits. The log intent was queued before the
    primary's shard on the same store, so the shard's commit covers
    it: that is what lets the fan-out start before the intent is
    durable."""
    async def body():
        c, cl, io = await _cluster(tmp_path, "bluestore", pool)
        try:
            await io.write_full("warm", b"w" * 70_000)
            pg = cl.osdmap.object_to_pg("p", "x")
            primary = c.osds[cl.osdmap.primary(pg)]
            order = []
            real = primary.store.queue_transaction

            def queue_transaction(txn):
                i = len(order)
                order.append(f"queued{i}")
                txn.register_on_commit(lambda: order.append(f"commit{i}"))
                return real(txn)
            primary.store.queue_transaction = queue_transaction
            del sent[:]
            syncs.only = primary.store._thread  # its commit thread alone
            syncs.hold()
            write = asyncio.create_task(io.write_full("x", b"v" * 70_000))
            await asyncio.sleep(0.6)
            assert [m for m in sent if m in SUB_REPLIES] \
                == sorted(SUB_REPLIES & set(sent)) * 2      # both are in
            assert not write.done() and "MOSDOpReply" not in sent
            assert not any(e.startswith("commit") for e in order)
            syncs.release()
            await asyncio.wait_for(write, 20)
            # the intent (the first transaction the write queued here)
            # committed no later than the shard's, in queue order
            n = len([e for e in order if e.startswith("queued")])
            assert n >= 2
            assert [e for e in order if e.startswith("commit")] \
                == [f"commit{i}" for i in range(n)]
        finally:
            syncs.release()
            await c.stop()
    run(body())


async def _restart_all(c) -> None:
    for i in list(c.osds):
        await c.kill_osd(i)
    for i in range(3):
        await c.start_osd(i)        # a fresh mount of the directory each


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_killed_before_the_commit_the_resend_lands(tmp_path, syncs, sent,
                                                   pool, monkeypatch):
    """Every store dies at its next commit, between the block sync and
    the KV batch, with a write in flight: nothing was acknowledged, the
    daemons come back on fresh mounts of the same directories without
    the write, the client sends it again and reads it back."""
    monkeypatch.setattr(RadosClient, "OP_TIMEOUT", 90.0)
    monkeypatch.setattr(RadosClient, "ATTEMPT_TIMEOUT", 2.0)

    async def body():
        c, _cl, io = await _cluster(tmp_path, "bluestore", pool)
        try:
            await io.write_full("base", b"b" * 70_000)
            await asyncio.sleep(0.1)
            del sent[:]
            for o in c.osds.values():
                o.store.fail_before_kv = True       # the kill, armed
            value = b"v" * 150_000
            write = asyncio.create_task(io.write_full("x", value))
            await asyncio.sleep(0.6)
            assert not write.done()
            assert set(sent) <= SUB_WRITES, sent    # nothing came back
            # a store that failed took its daemon down with it
            dead = [o for o in c.osds.values() if o.store.failed]
            assert dead and all(o._stopping for o in dead)
            await _restart_all(c)
            await asyncio.wait_for(write, 80)
            assert await io.read("x") == value
            assert await io.read("base") == b"b" * 70_000
        finally:
            await c.stop()
    run(body())


def test_killed_with_the_intent_durable_and_no_shard_the_resend_reexecutes(
        tmp_path, syncs, sent, monkeypatch):
    """The primary's log intent is its own transaction, queued before
    the fan-out: a kill can leave it durable with no shard anywhere.
    An erasure pool verifies a dup hit against the shards before it
    answers one (`ECBackend.verify_dup_committed`), so the client's
    resend is executed anew. (A replicated primary queues intent and
    data in one loop slice and answers a dup from the log alone: a
    kill that falls between the two groups there is PERF.md's open
    question.)"""
    from ceph_tpu.osd.ec_backend import ECBackend
    monkeypatch.setattr(RadosClient, "OP_TIMEOUT", 90.0)
    monkeypatch.setattr(RadosClient, "ATTEMPT_TIMEOUT", 2.0)
    real = ECBackend._encode_csums

    async def slow_encode(self, region):
        await asyncio.sleep(0.3)    # the intent's group forms alone
        return await real(self, region)

    async def body():
        c, cl, io = await _cluster(tmp_path, "bluestore", "erasure")
        try:
            await io.write_full("base", b"b" * 70_000)
            await asyncio.sleep(0.1)
            pg = cl.osdmap.object_to_pg("p", "x")
            primary = c.osds[cl.osdmap.primary(pg)]
            monkeypatch.setattr(ECBackend, "_encode_csums", slow_encode)
            syncs.hold()            # the intent's group stands in its sync
            value = b"v" * 150_000
            write = asyncio.create_task(io.write_full("x", value))
            await asyncio.sleep(0.15)
            groups = primary.store._groups
            for o in c.osds.values():
                o.store.fail_before_kv = True   # every LATER group dies
            await asyncio.sleep(0.5)            # the fan-out is queued
            syncs.release()
            await asyncio.sleep(0.3)
            assert not write.done()
            assert primary.store._groups_done == groups     # the intent's
            assert primary.store.failed and primary._stopping
            monkeypatch.setattr(ECBackend, "_encode_csums", real)
            await _restart_all(c)
            # the restarted primary knows the request
            again = c.osds[primary.whoami].pgs[
                next(k for k in c.osds[primary.whoami].pgs
                     if (k.pool, k.ps) == (pg.pool, pg.ps))]
            assert any(e.oid == "x" for e in again.log.entries)
            await asyncio.wait_for(write, 80)
            assert await io.read("x") == value
        finally:
            syncs.release()
            await c.stop()
    run(body())


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_a_clients_whole_object_writes_reach_the_block_file_by_reference(
        tmp_path, pool):
    """`write_full` of new objects whose shards (1 MiB over k = 2) and
    replicas are whole units: every block byte the stores write is
    written from the buffer the transaction was given, by the commit
    threads; none is copied on the way. The benchmark's BlueStore cell
    is this traffic, and `PERF.md` takes its share from `bstore_txc`."""
    async def body():
        c, _cl, io = await _cluster(tmp_path, "bluestore", pool)
        try:
            await io.write_full("warm", b"w" * 70_000)
            before = {i: o.store.stats() for i, o in c.osds.items()}
            values = {f"o{n}": bytes([n]) * (1 << 20) for n in range(4)}
            await asyncio.gather(*(io.write_full(k, v)
                                   for k, v in values.items()))
            for k, v in values.items():
                assert await io.read(k) == v
            wrote = by_ref = 0
            for i, o in c.osds.items():
                st = o.store.stats()
                wrote += st["block_bytes_written"] \
                    - before[i]["block_bytes_written"]
                by_ref += st["block_bytes_by_ref"] \
                    - before[i]["block_bytes_by_ref"]
                assert st["block_writes"] > before[i]["block_writes"]
            assert wrote == 4 * (3 << 19 if pool == "erasure" else 3 << 20)
            assert by_ref == wrote
        finally:
            await c.stop()
    run(body())


def test_the_admin_socket_serves_the_stores_counters(tmp_path):
    from ceph_tpu.osd.daemon import OSD
    from ceph_tpu.utils.admin_socket import admin_command

    async def body():
        c, _cl, io = await _cluster(tmp_path, "bluestore", "replicated")
        extra = None
        try:
            sock = str(tmp_path / "osd0.asok")
            await c.kill_osd(0)
            extra = OSD(0, c.mon_addrs, store=BlueStore(str(tmp_path / "osd0")),
                        admin_socket_path=sock)
            await extra.start()
            c.osds[0] = extra
            await io.write_full("a", b"a" * 70_000)
            out = await asyncio.to_thread(admin_command, sock, "store stats")
            got = out["result"]
            assert got["store"] == "BlueStore"
            assert got["acks_before_sync"] == 0
            assert set(got) >= {"txcs", "kv_syncs", "block_syncs",
                                "kv_fsyncs", "block_bytes_written",
                                "kv_bytes_written", "memtable_flushes",
                                "compactions"}
            assert got["kv_syncs"] >= 1 and got["kv_bytes_written"] > 0
        finally:
            await c.stop()
    run(body())


@pytest.mark.parametrize("how", ["write_full", "recovery_push"])
def test_a_shards_extents_keep_the_checksums_it_arrived_with(
        tmp_path, fast_timers, how):
    """An EC `write_full`'s sub-write and a recovery push onto BlueStore
    carry the shard's `csum` attr (crc32c of each 4 KiB chunk, taken at
    the encode) into `Transaction.write`: the onode's extents hold those
    very numbers, the store computed none of its own for them, every
    read verifies against them, and a deep scrub of the PG finds
    nothing."""
    import json

    async def body():
        c = ClusterHarness(tmp_path, n_osds=4, store_factory=lambda i:
                           BlueStore(str(tmp_path / f"osd{i}")))
        await c.start()
        try:
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "p22",
                              "profile": {"plugin": "jerasure", "k": "2",
                                          "m": "2"}})
            await cl.pool_create("p", pg_num=2, pool_type="erasure",
                                 erasure_code_profile="p22")
            io = cl.ioctx("p")
            await io.write_full("warm", b"w" * 70_000)
            if how == "recovery_push":
                await c.kill_osd(3)
                await c.wait_osd_down(3)
            values = {f"o{n}": bytes([n + 1]) * (1 << 20) for n in range(4)}
            before = {i: o.store.stats() for i, o in c.osds.items()}
            await asyncio.gather(*(io.write_full(k, v)
                                   for k, v in values.items()))
            if how == "recovery_push":
                # a new store object on the old directory: every block
                # it writes from here on is a push's
                back = await c.start_osd(
                    3, store=BlueStore(str(tmp_path / "osd3")))
                before[3] = back.store.stats()
                deadline = asyncio.get_running_loop().time() + 30
                # four OSDs for k + m = 4: it holds a shard of them all
                while not set(values) <= {
                        oid for pg in back.pgs.values()
                        for oid in pg.list_objects()}:
                    assert asyncio.get_running_loop().time() < deadline, \
                        "recovery incomplete"
                    await asyncio.sleep(0.2)
            seen = 0
            for i, osd in c.osds.items():
                if how == "recovery_push" and i != 3:
                    continue
                for pg in osd.pgs.values():
                    if osd.whoami not in pg.acting:
                        continue
                    for oid in set(values) & set(pg.list_objects()):
                        cid, gh = pg.backend.coll(), pg.backend.ghobject(oid)
                        on = osd.store._onode(cid, gh)
                        kept = [crc for _u, _c, crcs in on["extents"]
                                for crc in crcs]
                        assert kept == json.loads(
                            osd.store.getattr(cid, gh, "csum"))
                        assert len(kept) == (1 << 19) // 4096
                        assert len(osd.store.read(cid, gh)) == 1 << 19
                        seen += 1
                st = osd.store.stats()
                wrote = st["block_bytes_written"] \
                    - before[i]["block_bytes_written"]
                assert wrote > 0 and wrote == st["csum_bytes_reused"] \
                    - before[i]["csum_bytes_reused"]
            assert seen == (4 if how == "recovery_push" else 16)
            for k, v in values.items():
                assert await io.read(k) == v
            for osd in c.osds.values():
                for pg in osd.pgs.values():
                    if pg.primary == osd.whoami:
                        res = await pg.scrub(deep=True)
                        assert res["errors"] == 0, res
        finally:
            await c.stop()
    run(body())
