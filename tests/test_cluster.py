"""Single-host cluster integration tests: real daemons, real sockets,
one process (the reference's qa/standalone/ceph-helpers.sh tier —
test-erasure-code.sh boots mon+osds and writes/rereads with chunks
deleted; here the replicated path is the first slice).

Scenarios from the r3 verdict item #3: boot 1 mon + 3 osds, create a
pool, write/read 100 objects through the librados-subset client, kill
one osd (heartbeat failure reports -> mon marks it down -> new epoch ->
re-peering) and keep writing/reading.
"""
from __future__ import annotations

import asyncio

import pytest

from ceph_tpu.mon import MonMap, Monitor
from ceph_tpu.mon.paxos import Paxos
from ceph_tpu.msg.messenger import Connection
from ceph_tpu.osd.daemon import OSD
from ceph_tpu.rados import RadosClient

from tests.test_mon import free_ports


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def fast_timers(monkeypatch):
    monkeypatch.setattr(Paxos, "ELECTION_TIMEOUT", 0.15)
    monkeypatch.setattr(Paxos, "LEASE_INTERVAL", 0.2)
    monkeypatch.setattr(Paxos, "LEASE_TIMEOUT", 1.0)
    monkeypatch.setattr(Paxos, "ACCEPT_TIMEOUT", 0.8)
    monkeypatch.setattr(Connection, "KEEPALIVE_INTERVAL", 0.3)
    monkeypatch.setattr(Connection, "KEEPALIVE_TIMEOUT", 1.5)
    monkeypatch.setattr(Connection, "PARK_TIMEOUT", 2.0)
    monkeypatch.setattr(OSD, "HB_INTERVAL", 0.25)
    monkeypatch.setattr(OSD, "HB_GRACE", 1.2)


class ClusterHarness:
    """run_mon + run_osd equivalent (qa/standalone/ceph-helpers.sh)."""

    def __init__(self, tmp_path, n_mons: int = 1, n_osds: int = 3,
                 store_factory=None):
        ports = free_ports(n_mons)
        self.monmap = MonMap({f"m{i}": ("127.0.0.1", ports[i])
                              for i in range(n_mons)})
        self.tmp_path = tmp_path
        self.mons: dict[str, Monitor] = {}
        self.osds: dict[int, OSD] = {}
        self.n_osds = n_osds
        self.store_factory = store_factory
        self.clients: list[RadosClient] = []

    @property
    def mon_addrs(self):
        return list(self.monmap.mons.values())

    async def start(self) -> None:
        for name in self.monmap.mons:
            mon = Monitor(name, self.monmap,
                          store_path=str(self.tmp_path / f"mon.{name}"))
            self.mons[name] = mon
            await mon.start()
        # wait for a working quorum before booting osds
        deadline = asyncio.get_running_loop().time() + 20
        while not any(m.paxos.is_leader() and m.paxos.is_active()
                      for m in self.mons.values()):
            if asyncio.get_running_loop().time() > deadline:
                raise TimeoutError("no mon leader")
            await asyncio.sleep(0.05)
        for i in range(self.n_osds):
            await self.start_osd(i)

    async def start_osd(self, i: int, store=None) -> OSD:
        if store is None and self.store_factory is not None:
            store = self.store_factory(i)
        osd = OSD(i, self.mon_addrs, store=store)
        self.osds[i] = osd
        await osd.start()
        return osd

    async def kill_osd(self, i: int) -> None:
        await self.osds.pop(i).stop()

    async def client(self) -> RadosClient:
        c = RadosClient(self.mon_addrs)
        await c.connect()
        self.clients.append(c)
        return c

    async def wait_osd_down(self, i: int, timeout: float = 20.0) -> None:
        """Wait until every surviving osd's map shows osd.i down."""
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            maps = [o.osdmap for o in self.osds.values()]
            if maps and all(i in m.osds and not m.osds[i].up for m in maps):
                return
            if asyncio.get_running_loop().time() > deadline:
                raise TimeoutError(f"osd.{i} never marked down")
            await asyncio.sleep(0.1)

    async def stop(self) -> None:
        # each stop is BOUNDED: a daemon wedged mid-teardown (rare
        # thrash aftermath) must not hang the whole harness forever
        for c in self.clients:
            try:
                await asyncio.wait_for(c.shutdown(), 20)
            except Exception:
                pass
        for osd in list(self.osds.values()):
            try:
                await asyncio.wait_for(osd.stop(), 20)
            except Exception:
                pass
        for mon in self.mons.values():
            try:
                await asyncio.wait_for(mon.stop(), 20)
            except Exception:
                pass


def test_replicated_pool_end_to_end(tmp_path):
    """1 mon + 3 osds; write/read/list/stat/delete 100 objects."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=8, size=3)
            io = cl.ioctx("rbd")
            payloads = {f"obj{i:03d}": (f"payload-{i:03d}-".encode() * 17)
                        for i in range(100)}
            for oid, data in payloads.items():
                await io.write_full(oid, data)
            for oid, data in payloads.items():
                assert await io.read(oid) == data
            st = await io.stat("obj007")
            assert st["size"] == len(payloads["obj007"])
            listed = await io.list_objects()
            assert listed == sorted(payloads)
            await io.remove("obj000")
            with pytest.raises(Exception):
                await io.read("obj000")
            # the write actually replicated: every osd holds every object
            counts = []
            for osd in c.osds.values():
                n = sum(len(pg.list_objects()) for pg in osd.pgs.values()
                        if pg.state in ("active", "replica"))
                counts.append(n)
            assert sum(counts) == 3 * 99, counts
        finally:
            await c.stop()
    run(body())


def test_osd_death_cluster_survives(tmp_path):
    """Kill one osd: failure reports mark it down, writes/reads continue
    on the surviving acting sets."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=8, size=3)
            io = cl.ioctx("rbd")
            for i in range(30):
                await io.write_full(f"pre{i:02d}", b"x" * 500 + bytes([i]))
            await c.kill_osd(2)
            await c.wait_osd_down(2)
            # old data still readable, new writes land on survivors
            for i in range(30):
                assert await io.read(f"pre{i:02d}") == b"x" * 500 + bytes([i])
            for i in range(30):
                await io.write_full(f"post{i:02d}", b"y" * 300 + bytes([i]))
            for i in range(30):
                assert (await io.read(f"post{i:02d}")
                        == b"y" * 300 + bytes([i]))
        finally:
            await c.stop()
    run(body())


def test_ec_pool_end_to_end_and_degraded_read(tmp_path):
    """k=2,m=1 erasure pool with the tpu plugin in situ: writes stripe
    through the EC backend to positional shards; killing one shard OSD
    still serves reads via reconstruct (minimum_to_decode + batched
    decode), the reference test-erasure-code.sh contract."""
    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "tpuprof",
                              "profile": {"plugin": "tpu", "k": "2",
                                          "m": "1"}})
            await cl.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                 erasure_code_profile="tpuprof")
            io = cl.ioctx("ecpool")
            # 2-stripe objects (stripe_width = 2*4096): same jit shape
            payloads = {f"e{i:02d}": bytes([i]) * 9000 for i in range(12)}
            for oid, data in payloads.items():
                await io.write_full(oid, data)
            for oid, data in payloads.items():
                assert await io.read(oid) == data
            # each live osd holds chunk-shards, not whole objects
            chunk = 4096
            for osd in c.osds.values():
                for pg in osd.pgs.values():
                    for oid in pg.list_objects():
                        got = osd.store.read(pg.backend.coll(),
                                             pg.backend.ghobject(oid))
                        assert len(got) % chunk == 0 and \
                            len(got) < max(len(d) for d in payloads.values())
            st = await io.stat("e03")
            assert st["size"] == 9000
            # degraded read: kill one shard osd, reads reconstruct
            await c.kill_osd(2)
            await c.wait_osd_down(2)
            for oid, data in payloads.items():
                assert await io.read(oid) == data, f"degraded read {oid}"
        finally:
            await c.stop()
    run(body())


def test_ec_recovery_reconstructs_lost_shards(tmp_path):
    """k=2,m=2 over 4 osds: writes continue degraded (min_size=3) while
    one osd is down; on restart, peering reconstructs its positional
    chunks from survivors and pushes them (RecoveryOp semantics)."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=4)
        try:
            await c.start()
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "jprof",
                              "profile": {"plugin": "jerasure", "k": "2",
                                          "m": "2",
                                          "technique": "reed_sol_van"}})
            await cl.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                 erasure_code_profile="jprof")
            io = cl.ioctx("ecpool")
            for i in range(8):
                await io.write_full(f"pre{i}", bytes([i + 1]) * 5000)
            victim = c.osds[3]
            store = victim.store
            await c.kill_osd(3)
            await c.wait_osd_down(3)
            for i in range(8):   # degraded writes (3 of 4 shards live)
                await io.write_full(f"deg{i}", bytes([i + 101]) * 5000)
            for i in range(4):   # overwrites the dead osd must NOT keep
                await io.write_full(f"pre{i}", bytes([i + 51]) * 6000)
            await c.start_osd(3, store=store)
            # recovery: osd.3 regains a chunk for every object in its PGs
            deadline = asyncio.get_running_loop().time() + 25
            while True:
                osd = c.osds[3]
                missing = []
                for pg in osd.pgs.values():
                    if osd.whoami not in pg.acting:
                        continue
                    primary = c.osds.get(pg.primary)
                    if primary is None:
                        continue
                    ppg = primary.pgs.get(pg.pgid)
                    if ppg is None:
                        continue
                    want = set(ppg.list_objects())
                    have = set(pg.list_objects())
                    missing.extend(want - have)
                if not missing:
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(f"ec recovery incomplete: "
                                         f"{missing[:6]}")
                await asyncio.sleep(0.2)
            for i in range(8):
                assert await io.read(f"deg{i}") == bytes([i + 101]) * 5000
            for i in range(4):
                assert await io.read(f"pre{i}") == bytes([i + 51]) * 6000
            for i in range(4, 8):
                assert await io.read(f"pre{i}") == bytes([i + 1]) * 5000
            # the restarted osd's chunks must carry the overwrite's
            # version, not its pre-death stale one (recovery must never
            # hand a returning shard its own old chunk back)
            import json as _json
            osd3 = c.osds[3]
            for pg in osd3.pgs.values():
                for oid in pg.list_objects():
                    if not oid.startswith("pre"):
                        continue
                    attrs = osd3.store.getattrs(pg.backend.coll(),
                                                pg.backend.ghobject(oid))
                    primary = c.osds[pg.primary]
                    pattrs = primary.pgs[pg.pgid].backend.read_for_push(
                        oid)[1]
                    assert _json.loads(attrs["version"]) == \
                        _json.loads(pattrs["version"]), oid
        finally:
            await c.stop()
    run(body())


def test_restart_within_grace_rolls_the_interval(tmp_path):
    """An OSD killed and revived before the mon ever marks it down gets
    a new boot address but the SAME acting sets: peering must still
    re-run (the reference's check_new_interval treats a changed up_from
    as a new interval via PastIntervals), or sub-ops lost in the
    restart window are never repaired."""
    async def body():
        c = ClusterHarness(tmp_path, n_osds=3)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=8, size=3)
            io = cl.ioctx("rbd")
            for i in range(8):
                await io.write_full(f"o{i}", b"x" * 2000)
            before = {pg.pgid: pg.last_epoch_started
                      for o in c.osds.values() if o.whoami != 2
                      for pg in o.pgs.values()
                      if pg.is_primary() and 2 in pg.acting}
            assert before, "no primary has osd.2 in acting"
            store = c.osds[2].store
            await c.kill_osd(2)
            await c.start_osd(2, store=store)   # well inside HB_GRACE
            deadline = asyncio.get_running_loop().time() + 15
            while True:
                after = {pg.pgid: pg.last_epoch_started
                         for o in c.osds.values() if o.whoami != 2
                         for pg in o.pgs.values()
                         if pg.is_primary() and 2 in pg.acting
                         and pg.state == "active"}
                if after and all(after.get(pgid, 0) > les
                                 for pgid, les in before.items()):
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(
                        f"interval never rolled: {before} -> {after}")
                await asyncio.sleep(0.1)
            for i in range(8):      # cluster still fully serves
                assert await io.read(f"o{i}") == b"x" * 2000
        finally:
            await c.stop()
    run(body())


def test_ec_delete_while_osd_down_is_not_resurrected(tmp_path):
    """A delete committed while one shard-holder is down must stay a
    delete after the holder revives: recovery pushes the DELETION to the
    behind peer. Reconstructing from the surviving shards' rollback
    generations instead resurrects a lone stale shard — every later read
    then EIOs forever (1 < k shards yet not ENOENT). Found by the
    thrashing model checker (ref: recovery honoring delete log
    entries, src/osd/PGLog.h missing is_delete)."""
    async def body():
        from ceph_tpu.rados import ObjectNotFound
        c = ClusterHarness(tmp_path, n_osds=4)
        try:
            await c.start()
            cl = await c.client()
            await cl.command({"prefix": "osd erasure-code-profile set",
                              "name": "jprof",
                              "profile": {"plugin": "jerasure", "k": "2",
                                          "m": "2",
                                          "technique": "reed_sol_van"}})
            await cl.pool_create("ecpool", pg_num=4, pool_type="erasure",
                                 erasure_code_profile="jprof")
            io = cl.ioctx("ecpool")
            for i in range(6):
                await io.write_full(f"o{i}", bytes([i + 1]) * 5000)
            victim = c.osds[3]
            store = victim.store
            await c.kill_osd(3)
            await c.wait_osd_down(3)
            for i in range(6):          # deletes commit on 3 live shards
                await io.remove(f"o{i}")
            await c.start_osd(3, store=store)
            # convergence: the revived osd must drop its stale shards,
            # and reads must settle on ENOENT — never a wedged EIO
            deadline = asyncio.get_running_loop().time() + 25
            while True:
                osd3 = c.osds[3]
                stale = [oid for pg in osd3.pgs.values()
                         if osd3.whoami in pg.acting
                         for oid in pg.list_objects()
                         if oid.startswith("o")]
                if not stale:
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(
                        f"revived osd still holds deleted objects' "
                        f"shards: {stale[:6]}")
                await asyncio.sleep(0.2)
            for i in range(6):
                try:
                    await io.read(f"o{i}")
                    raise AssertionError(f"o{i}: read succeeded after "
                                         f"committed delete")
                except ObjectNotFound:
                    pass
        finally:
            await c.stop()
    run(body())


@pytest.mark.parametrize("backend", ["memstore", "filestore"])
def test_osd_restart_recovers_by_log(tmp_path, backend):
    """Kill an osd, write while it is down, restart it with the same
    store: peering pushes it the writes it missed (log-driven recovery,
    PGLog::merge_log semantics) and it serves reads again. With the
    filestore backend the restart builds a FRESH store instance on the
    same directory — true process-restart semantics (checkpoint + WAL
    replay feeding PG meta/log recovery)."""
    from ceph_tpu.objectstore import FileStore
    factory = (lambda i: FileStore(str(tmp_path / f"osd{i}"))) \
        if backend == "filestore" else None

    async def body():
        c = ClusterHarness(tmp_path, store_factory=factory)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=8, size=3)
            io = cl.ioctx("rbd")
            for i in range(20):
                await io.write_full(f"a{i:02d}", b"first" + bytes([i]))
            victim = c.osds[1]
            store = victim.store
            await c.kill_osd(1)
            await c.wait_osd_down(1)
            # writes the dead osd misses (overwrites + fresh objects)
            for i in range(20):
                await io.write_full(f"a{i:02d}", b"second" + bytes([i]))
            for i in range(10):
                await io.write_full(f"b{i:02d}", b"new" + bytes([i]))
            # restart from the surviving store: boots, re-peers, recovers
            await c.start_osd(1, store=(factory(1) if factory else store))
            deadline = asyncio.get_running_loop().time() + 20
            while True:
                osd = c.osds[1]
                stale = []
                for pg in osd.pgs.values():
                    if pg.state not in ("active", "replica"):
                        continue
                    for oid in pg.list_objects():
                        data = bytes(osd.store.read(
                            pg.backend.coll(), pg.backend.ghobject(oid)))
                        if oid.startswith("a") and not \
                                data.startswith(b"second"):
                            stale.append(oid)
                have = {oid for pg in osd.pgs.values()
                        for oid in pg.list_objects()}
                want = {f"a{i:02d}" for i in range(20)} \
                    | {f"b{i:02d}" for i in range(10)}
                if not stale and want <= have:
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(
                        f"recovery incomplete: stale={stale[:5]} "
                        f"missing={sorted(want - have)[:5]}")
                await asyncio.sleep(0.2)
        finally:
            await c.stop()
    run(body())


def test_primary_behind_log_tail_backfills(tmp_path, monkeypatch):
    """A restarted primary whose log head predates the auth peer's log
    TAIL must backfill the full object set instead of trusting a merge
    that cannot see the missed window (ADVICE r4: silent write loss).
    Deletes that happened while it was down must also take effect."""
    from ceph_tpu.osd.pglog import PGLog
    monkeypatch.setattr(PGLog, "MAX_ENTRIES", 8)

    async def body():
        c = ClusterHarness(tmp_path)
        try:
            await c.start()
            cl = await c.client()
            await cl.pool_create("rbd", pg_num=1, size=3)
            io = cl.ioctx("rbd")
            for i in range(5):
                await io.write_full(f"o{i}", b"v1-" + bytes([i]))
            from ceph_tpu.crush.osdmap import PG as PGId
            pool = cl.osdmap.get_pool("rbd")
            victim = cl.osdmap.primary(PGId(pool.id, 0))
            store = c.osds[victim].store
            await c.kill_osd(victim)
            await c.wait_osd_down(victim)
            # slide the survivors' log window far past the victim's head:
            # > MAX_ENTRIES writes, including overwrites, fresh objects,
            # and a delete
            await io.remove("o0")
            for r in range(3):
                for i in range(1, 5):
                    await io.write_full(f"o{i}", b"v2-%d-" % r + bytes([i]))
            for i in range(6):
                await io.write_full(f"n{i}", b"new-" + bytes([i]))
            await c.start_osd(victim, store=store)
            deadline = asyncio.get_running_loop().time() + 25
            want = {f"o{i}" for i in range(1, 5)} | {f"n{i}" for i in range(6)}
            while True:
                osd = c.osds[victim]
                pgs = [pg for pg in osd.pgs.values()
                       if pg.state == "active" and pg.is_primary()]
                have = {oid for pg in pgs for oid in pg.list_objects()}
                if pgs and have == want:
                    break
                if asyncio.get_running_loop().time() > deadline:
                    raise AssertionError(
                        f"backfill wrong: have={sorted(have)} "
                        f"want={sorted(want)} "
                        f"states={[pg.state for pg in osd.pgs.values()]}")
                await asyncio.sleep(0.2)
            # client-visible state is the authoritative one
            assert sorted(await io.list_objects()) == sorted(want)
            for i in range(1, 5):
                assert (await io.read(f"o{i}")).startswith(b"v2-2-")
            import pytest as _pytest
            from ceph_tpu.rados import ObjectNotFound
            with _pytest.raises(ObjectNotFound):
                await io.read("o0")
        finally:
            await c.stop()
    run(body())


def test_authed_cluster_end_to_end(tmp_path):
    """cephx-lite across the whole cluster: mon+osds+client share a
    secret and everything works; a wrong-key client cannot connect."""
    async def body():
        import pytest as _pytest
        key = b"cluster-shared-secret"
        ports = free_ports(1)
        monmap = MonMap({"m0": ("127.0.0.1", ports[0])})
        mon = Monitor("m0", monmap, store_path=str(tmp_path / "mon"),
                      auth_key=key)
        await mon.start()
        while not (mon.paxos.is_leader() and mon.paxos.is_active()):
            await asyncio.sleep(0.05)
        osds = []
        try:
            for i in range(3):
                osd = OSD(i, list(monmap.mons.values()), auth_key=key)
                await osd.start()
                osds.append(osd)
            cl = RadosClient(list(monmap.mons.values()), auth_key=key)
            await cl.connect()
            await cl.pool_create("rbd", pg_num=4, size=3)
            io = cl.ioctx("rbd")
            await io.write_full("secret-obj", b"payload")
            assert await io.read("secret-obj") == b"payload"
            await cl.shutdown()
            # wrong key: the mon rejects the session; connect times out
            evil = RadosClient(list(monmap.mons.values()),
                               auth_key=b"not-the-key")
            with _pytest.raises(Exception):
                await asyncio.wait_for(evil.connect(), 5)
            await evil.shutdown()
        finally:
            for osd in osds:
                await osd.stop()
            await mon.stop()
    run(body())
