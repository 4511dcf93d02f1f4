"""No acknowledgement leaves an OSD before the store's commit: a shard's
sub-op reply, a replica's, the primary's own shard and with it the
client's reply all wait for `on_commit`. On a store that commits inside
`queue_transaction` (MemStore) the order of events is what it was. An
erasure pool's replica queues ONE transaction a sub-write: it holds the
shard's bytes and the PG's log entry, and the reply leaves from its
commit."""
from __future__ import annotations

import asyncio

import pytest

from ceph_tpu.msg import messenger
from ceph_tpu.objectstore.bluestore import BlueStore
from ceph_tpu.objectstore.store import Op, StoreError, Transaction
from ceph_tpu.osd.pg import PGMETA_OID
from ceph_tpu.osd.pglog import LogEntry, PGLog
from ceph_tpu.rados import RadosClient
from ceph_tpu.utils import crash

from tests.test_bluestore_commit import Syncs, syncs  # noqa: F401
from tests.test_cluster import ClusterHarness, fast_timers, run  # noqa: F401


@pytest.fixture(autouse=True)
def no_crash_records_left():
    """A daemon whose store is killed here posts a crash record, and the
    registry is the process's: a later test in this worker that reads
    health (tests/test_mgr_report.py) would find RECENT_CRASH."""
    yield
    crash.reset()

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


def _pg_of(osd, pg):
    """The OSD's instance of the map's placement group `pg`."""
    return next(inst for key, inst in osd.pgs.items()
                if (key.pool, key.ps) == (pg.pool, pg.ps))


def _watch_transactions(osd, sent) -> list[dict]:
    """Every transaction the OSD's store is given from here on: what it
    writes (`wrote`: object names), the PG-log keys it sets (`log_keys`)
    and, once it has committed, how many sub-op replies had left any
    daemon when its own callbacks had run (`replies_at_commit`)."""
    seen: list[dict] = []
    real = osd.store.queue_transaction

    def queue_transaction(txn):
        rec = {"wrote": [op[2].name for op in txn.ops if op[0] is Op.WRITE],
               "log_keys": [k for op in txn.ops
                            if op[0] is Op.OMAP_SETKEYS
                            and op[2].name == PGMETA_OID
                            for k in op[3] if k.startswith(PGLog.KEY_PREFIX)],
               "replies_at_commit": None}
        seen.append(rec)
        # registered last: it runs after what the OSD registered
        txn.register_on_commit(lambda: rec.update(
            replies_at_commit=len([m for m in sent if m in SUB_REPLIES])))
        return real(txn)
    osd.store.queue_transaction = queue_transaction
    return seen


def _rode(c) -> int:
    return sum(o.perf.dump()["meta_rode_txn"] for o in c.osds.values())


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("kind", ["bluestore", "memstore"])
def test_no_ack_leaves_before_the_commit(tmp_path, syncs, sent, kind, pool):
    async def body():
        c, cl, io = await _cluster(tmp_path, kind, pool)
        try:
            await io.write_full("warm", b"w" * 70_000)  # peered, all imported
            del sent[:]
            primary = cl.osdmap.primary(cl.osdmap.object_to_pg("p", "x"))
            queued = {i: _watch_transactions(o, sent)
                      for i, o in c.osds.items() if i != primary}
            rode = _rode(c)
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
            if pool == "erasure":
                # a replica's sub-write is ONE transaction: the shard's
                # bytes and the PG's log entry are both in it, and its
                # reply left from that commit, neither before nor after
                # (the n-th replica to commit finds n replies sent)
                assert [len(txns) for txns in queued.values()] == [1, 1]
                txns = [t for (t,) in queued.values()]
                assert all(t["wrote"] == ["x"] and len(t["log_keys"]) == 1
                           for t in txns), txns
                assert sorted(t["replies_at_commit"] for t in txns) == [1, 2]
                assert _rode(c) - rode == 2     # a replica each
            else:
                assert _rode(c) == rode         # the replicated backend's
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
            again = _pg_of(c.osds[primary.whoami], pg)
            assert any(e.oid == "x" for e in again.log.entries)
            await asyncio.wait_for(write, 80)
            assert await io.read("x") == value
        finally:
            syncs.release()
            await c.stop()
    run(body())


def _on_a_fresh_mount(path: str, cid, gh, meta) -> tuple[bool, set[str]]:
    """Whether a second store mounted on the directory finds the
    object, and the PG-log keys it finds on the PG's meta object."""
    store = BlueStore(path)
    store.mount()
    try:
        return store.exists(cid, gh), {
            k for k in store.omap_get(cid, meta)
            if k.startswith(PGLog.KEY_PREFIX)}
    finally:
        store.umount()


def test_a_replica_killed_before_its_commit_keeps_neither_shard_nor_entry(
        tmp_path, syncs, sent, monkeypatch):
    """The atomicity that one transaction a sub-write buys. A replica
    has queued a sub-write (the shard reads back from it, its log holds
    the entry) and dies before that group's KV batch: a fresh mount of
    its directory finds NEITHER the shard NOR the log entry, where two
    transactions could leave the bytes without the entry that describes
    them. Of a write that was acknowledged before, the same mount finds
    both. The daemon comes back, the client's resend lands."""
    monkeypatch.setattr(RadosClient, "OP_TIMEOUT", 90.0)
    monkeypatch.setattr(RadosClient, "ATTEMPT_TIMEOUT", 2.0)

    async def body():
        c, cl, io = await _cluster(tmp_path, "bluestore", "erasure")
        try:
            await io.write_full("warm", b"w" * 70_000)
            pg = cl.osdmap.object_to_pg("p", "x")
            kept = next(f"kept{n}" for n in range(64) if
                        cl.osdmap.object_to_pg("p", f"kept{n}") == pg)
            await io.write_full(kept, b"k" * 70_000)    # acknowledged
            primary = c.osds[cl.osdmap.primary(pg)]
            r = next(i for i in c.osds if i != primary.whoami)
            replica = c.osds[r]
            inst = _pg_of(replica, pg)
            cid, meta = inst.backend.coll(), inst._meta_gh()
            gh = {o: inst.backend.ghobject(o) for o in ("x", kept)}
            queued = _watch_transactions(replica, sent)
            del sent[:]
            syncs.only = replica.store._thread
            syncs.hold()
            value = b"v" * 150_000
            write = asyncio.create_task(io.write_full("x", value))
            await asyncio.sleep(0.6)
            assert not write.done()
            # queued, one transaction, readable, and not committed
            assert [t["wrote"] for t in queued] == [["x"]]
            assert queued[0]["replies_at_commit"] is None
            assert replica.store.exists(cid, gh["x"])
            key = {o: PGLog.entry_key(next(
                e.version for e in inst.log.entries if e.oid == o))
                for o in ("x", kept)}
            assert queued[0]["log_keys"] == [key["x"]]
            replica.store.fail_before_kv = True     # the kill, armed
            syncs.release()
            await asyncio.sleep(0.3)
            assert replica.store.failed and replica._stopping
            assert not write.done()
            await c.kill_osd(r)
            has, keys = _on_a_fresh_mount(str(tmp_path / f"osd{r}"),
                                          cid, gh["x"], meta)
            assert not has and key["x"] not in keys
            has, keys = _on_a_fresh_mount(str(tmp_path / f"osd{r}"),
                                          cid, gh[kept], meta)
            assert has and key[kept] in keys
            await c.start_osd(r)
            await asyncio.wait_for(write, 80)
            assert await io.read("x") == value
            assert await io.read(kept) == b"k" * 70_000
        finally:
            syncs.release()
            await c.stop()
    run(body())


@pytest.mark.parametrize("dirty", ["incremental", "full"])
@pytest.mark.parametrize("kind", ["bluestore", "memstore"])
def test_the_meta_a_transaction_is_given_is_the_meta_persisted_alone(
        tmp_path, kind, dirty):
    """`PG.append_meta` appends to the transaction it is given exactly
    the ops that `persist_meta` queues alone for the same dirty state,
    after whatever the transaction held, and counts which of the two a
    persist was."""
    async def body():
        c, cl, io = await _cluster(tmp_path, kind, "erasure")
        try:
            await io.write_full("x", b"v" * 70_000)
            pg = cl.osdmap.object_to_pg("p", "x")
            osd = c.osds[cl.osdmap.primary(pg)]
            inst = _pg_of(osd, pg)
            inst.log.append(LogEntry(version=inst.next_version(),
                                     op="modify", oid="y"))
            if dirty == "full":
                inst.log.restore_dirty(True, {})    # as an adopted log is
            cid, gh = inst.backend.coll(), inst.backend.ghobject("y")
            given = Transaction().touch(cid, gh)
            taken = inst.append_meta(given)
            assert taken[0] == (dirty == "full")
            assert inst.log.take_dirty() == (False, {})     # consumed
            inst.log.restore_dirty(*taken)
            queued = []
            real = osd.store.queue_transaction
            osd.store.queue_transaction = \
                lambda txn: (queued.append(txn), real(txn))[1]
            before = osd.perf.dump()
            inst.persist_meta()
            assert len(queued) == 1
            assert given.ops[0] == (Op.TOUCH, cid, gh)
            assert given.ops[1:] == queued[0].ops
            kinds = [op[0] for op in queued[0].ops]
            assert kinds[0] is Op.SETATTRS and Op.OMAP_SETKEYS in kinds
            sets = next(op[3] for op in queued[0].ops
                        if op[0] is Op.OMAP_SETKEYS)
            assert len(sets) == (len(inst.log.entries) if dirty == "full"
                                 else 1)
            inst.log.append(LogEntry(version=inst.next_version(),
                                     op="modify", oid="z"))
            inst.persist_meta(txn=Transaction().touch(cid, gh))
            assert queued[1].ops[0] == (Op.TOUCH, cid, gh)
            assert len(queued[1].ops) == 3      # and the attr, and a key
            after = osd.perf.dump()
            assert (after["meta_alone_txn"] - before["meta_alone_txn"],
                    after["meta_rode_txn"] - before["meta_rode_txn"]) \
                == (1, 1)
        finally:
            await c.stop()
    run(body())


@pytest.mark.parametrize("rides", [False, True], ids=["alone", "rides"])
@pytest.mark.parametrize("dirty", ["incremental", "full"])
def test_a_prepare_that_raises_hands_the_logs_delta_back(tmp_path, dirty,
                                                         rides):
    """The store refuses the transaction (a `prepare` that raises): the
    dirty delta that `append_meta` took is the log's again, whether the
    meta was queued alone or rode a data transaction, no persist is
    counted, and the next persist writes it."""
    async def body():
        c, cl, io = await _cluster(tmp_path, "memstore", "erasure")
        try:
            await io.write_full("x", b"v" * 70_000)
            pg = cl.osdmap.object_to_pg("p", "x")
            osd = c.osds[cl.osdmap.primary(pg)]
            inst = _pg_of(osd, pg)
            entry = LogEntry(version=inst.next_version(), op="modify",
                             oid="y")
            inst.log.append(entry)
            if dirty == "full":
                inst.log.restore_dirty(True, {})
            want = (dirty == "full", {PGLog.entry_key(entry.version): entry})
            real = osd.store.queue_transaction

            def refuse(txn):
                raise StoreError("ENOSPC", "no room to prepare")
            osd.store.queue_transaction = refuse
            before = osd.perf.dump()
            acked = []
            given = Transaction().touch(inst.backend.coll(),
                                        inst.backend.ghobject("y"))
            with pytest.raises(StoreError):
                inst.persist_meta(on_commit=lambda: acked.append(1),
                                  txn=given if rides else None)
            assert osd.perf.dump() == before and not acked
            osd.store.queue_transaction = real
            assert inst.log.take_dirty() == want
            inst.log.restore_dirty(*want)
            inst.persist_meta(on_commit=lambda: acked.append(2))
            assert acked == [2]
            assert PGLog.entry_key(entry.version) in osd.store.omap_get(
                inst.backend.coll(), inst._meta_gh())
        finally:
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
