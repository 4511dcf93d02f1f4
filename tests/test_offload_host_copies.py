"""A batch's two host copies ride the hop it already makes: the stacking
copy before H2D and each encode rider's shard-plane assembly after D2H
run on the staging-pool thread that serves the batch, beside the event
loop, and what they produce is what the inline path produces."""
from __future__ import annotations

import asyncio
import threading

import numpy as np
import pytest

from ceph_tpu import offload
from ceph_tpu.ec import registry
from ceph_tpu.native import ec_native
from ceph_tpu.offload import service as offload_service
from ceph_tpu.osd import ec_util
from ceph_tpu.utils import copytrack

from tests.test_cluster import run

C = 4096
SHAPES = [(8, 3, 128), (8, 3, 1), (2, 1, 4)]
_ids = [f"k{k}m{m}S{S}" for k, m, S in SHAPES]


def _impl(k, m):
    return registry.factory("tpu", {"k": str(k), "m": str(m)})


def _objects(k, S, n, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, S * k * C, dtype=np.uint8).tobytes()
            for _ in range(n)]


def _copied(*stages):
    snap = copytrack.snapshot()["stages"]
    return sum(snap[s]["copied_bytes"] for s in stages)


async def _coalesced(svc, sinfo, impl, objects):
    """The objects' shards through the service, all in one batch."""
    svc.linger_ms, svc.max_batch_bytes = 50.0, 64 << 20
    base = svc.stats["batches"]
    outs = await asyncio.gather(*[
        ec_util.encode_async(sinfo, impl, o, service=svc) for o in objects])
    await svc.drain()
    assert svc.stats["batches"] - base == 1
    return outs


@pytest.fixture
def staging_ledger(monkeypatch):
    """Every page a slot hands out and takes back, in order."""
    ledger = []
    get, put = (offload_service._DeviceSlot.get_staging,
                offload_service._DeviceSlot.put_staging)

    def get_staging(slot, nbytes):
        buf = get(slot, nbytes)
        ledger.append(("get", id(buf)))
        return buf

    def put_staging(slot, buf):
        ledger.append(("put", id(buf)))
        return put(slot, buf)
    monkeypatch.setattr(offload_service._DeviceSlot, "get_staging",
                        get_staging)
    monkeypatch.setattr(offload_service._DeviceSlot, "put_staging",
                        put_staging)
    return ledger


@pytest.mark.parametrize("k,m,S", SHAPES, ids=_ids)
def test_shards_through_the_service_equal_the_inline_ones(k, m, S):
    async def body():
        impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
        objects = _objects(k, S, 3)
        refs = [ec_util.encode(sinfo, impl, o) for o in objects]
        outs = await _coalesced(offload.get_service(), sinfo, impl, objects)
        for out, ref in zip(outs, refs):
            assert sorted(out) == sorted(ref) == list(range(k + m))
            for i in ref:
                assert len(out[i]) == S * C
                assert bytes(out[i]) == bytes(ref[i])
    run(body(), timeout=120)


@pytest.mark.parametrize("k,m,S", SHAPES, ids=_ids)
def test_both_copies_run_on_the_staging_pool_thread(k, m, S, monkeypatch):
    async def body():
        impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
        svc = offload.get_service()
        objects = _objects(k, S, 3)
        await ec_util.encode_async(sinfo, impl, objects[0], service=svc)
        seen = []
        real = np.copyto

        def copyto(dst, src, *a, **kw):
            seen.append((threading.current_thread(), dst.nbytes))
            return real(dst, src, *a, **kw)

        def gather(src, first, count, out):
            seen.append((threading.current_thread(), out.nbytes))
            return real_gather(src, first, count, out)
        real_gather = ec_native.planes_from_stripes
        monkeypatch.setattr(np, "copyto", copyto)
        monkeypatch.setattr(ec_native, "planes_from_stripes", gather)
        await _coalesced(svc, sinfo, impl, objects)
        monkeypatch.setattr(np, "copyto", real)
        monkeypatch.setattr(ec_native, "planes_from_stripes", real_gather)
        # three riders' stripes stacked, and every strided plane of
        # theirs assembled: S stripes of k or m chunks are strided
        # unless there is one stripe or one chunk a stripe
        strided = (k if S > 1 and k > 1 else 0) + (m if S > 1 and m > 1
                                                   else 0)
        want = 3 * S * k * C + 3 * strided * S * C
        assert sum(n for _, n in seen) == want
        assert seen and all(t.name.startswith("ec-offload")
                            and t is not threading.main_thread()
                            for t, _ in seen)
    assert threading.current_thread() is threading.main_thread()
    run(body(), timeout=120)


@pytest.mark.parametrize("k,m,S", SHAPES, ids=_ids)
def test_the_ledger_counts_both_copies_wherever_they_are_made(
        k, m, S, monkeypatch):
    """`copytrack` counts the stacking copy and the planes from the
    staging-pool thread as it did from the loop; the service's inline
    bypass (`ec_offload_enabled` false) runs the finisher on the caller's
    thread and gives the same shards."""
    stages = ("buffer_to_staging", "reply_assemble")

    async def body():
        impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
        svc = offload.get_service()
        objects = _objects(k, S, 4)
        # what one object's strided planes come to, by `encode`, which
        # goes round the service
        planes = _copied(*stages)
        ref = ec_util.encode(sinfo, impl, objects[3])
        planes = _copied(*stages) - planes
        ledger0 = _copied(*stages)
        await _coalesced(svc, sinfo, impl, objects[:3])
        assert _copied(*stages) - ledger0 == 3 * S * k * C + 3 * planes
        ledger0, where = _copied(*stages), []
        real = ec_native.planes_from_stripes

        def gather(*a):
            where.append(threading.current_thread())
            return real(*a)
        monkeypatch.setattr(svc, "enabled", False)
        monkeypatch.setattr(ec_native, "planes_from_stripes", gather)
        out = await ec_util.encode_async(sinfo, impl, objects[3],
                                         service=svc)
        monkeypatch.undo()
        assert all(bytes(out[i]) == bytes(ref[i]) for i in ref)
        assert _copied(*stages) - ledger0 == planes
        assert all(t is threading.main_thread() for t in where)
        assert bool(where) == bool(planes)
    run(body(), timeout=120)


def test_a_lone_unpadded_job_is_still_handed_through_by_reference(
        staging_ledger):
    async def body():
        impl, sinfo = _impl(8, 3), ec_util.StripeInfo(8, 8 * C)
        svc = offload.get_service()
        data, = _objects(8, 128, 1)
        before = copytrack.snapshot()["stages"]["buffer_to_staging"]
        out = await ec_util.encode_async(sinfo, impl, data, service=svc)
        after = copytrack.snapshot()["stages"]["buffer_to_staging"]
        assert after["referenced_bytes"] - before["referenced_bytes"] \
            == len(data)
        assert after["copied_bytes"] == before["copied_bytes"]
        assert staging_ledger == []
        ref = ec_util.encode(sinfo, impl, data)
        assert all(bytes(out[i]) == bytes(ref[i]) for i in ref)
    run(body(), timeout=120)


def test_a_finisher_that_raises_fails_its_rider_alone(staging_ledger):
    async def body():
        impl = _impl(4, 2)
        svc = offload.get_service()
        svc.linger_ms = 50.0
        rng = np.random.default_rng(3)
        a, b = (rng.integers(0, 256, (4, 4, C), dtype=np.uint8)
                for _ in range(2))
        plain = await svc.encode(impl, b)
        where = []

        def bad(parity):
            where.append(threading.current_thread().name)
            raise ValueError("this rider's planes")

        def good(parity):
            return "mine", parity.copy()
        base = dict(svc.stats)
        got_a, got_b, got_c = await asyncio.gather(
            svc.encode(impl, a, finish=bad),
            svc.encode(impl, b, finish=good),
            svc.encode(impl, b), return_exceptions=True)
        await svc.drain()
        assert isinstance(got_a, ValueError) and where and \
            where[0].startswith("ec-offload")
        assert got_b[0] == "mine" and np.array_equal(got_b[1], plain)
        assert np.array_equal(got_c, plain)     # no finisher: its rows
        assert svc.stats["batches"] - base["batches"] == 1
        # the one page taken went back to its slot
        assert [w for w, _ in staging_ledger] == ["get", "put"]
        assert staging_ledger[0][1] == staging_ledger[1][1]
        assert svc._throttle.current == 0
    run(body(), timeout=120)


def test_a_rider_cancelled_while_its_finisher_runs_leaks_nothing(
        staging_ledger):
    async def body():
        impl = _impl(4, 2)
        svc = offload.get_service()
        svc.linger_ms = 20.0
        rng = np.random.default_rng(5)
        a, b = (rng.integers(0, 256, (4, 4, C), dtype=np.uint8)
                for _ in range(2))
        plain = await svc.encode(impl, b)
        started, release = threading.Event(), threading.Event()

        def held(parity):
            started.set()
            assert release.wait(30)
            return parity
        rider = asyncio.ensure_future(svc.encode(impl, a, finish=held))
        mate = asyncio.ensure_future(svc.encode(impl, b))
        while not started.is_set():
            await asyncio.sleep(0.001)
        rider.cancel()
        await asyncio.sleep(0.01)       # the loop runs beside the thread
        assert not mate.done()
        release.set()
        got = await asyncio.gather(rider, mate, return_exceptions=True)
        assert isinstance(got[0], asyncio.CancelledError)
        assert np.array_equal(got[1], plain)
        await svc.drain()
        assert not svc._tasks and not svc._buckets
        assert svc._throttle.current == 0
        assert [w for w, _ in staging_ledger] == ["get", "put"]
        # and the service serves the next batch from that page
        again = await asyncio.gather(svc.encode(impl, a),
                                     svc.encode(impl, b))
        assert np.array_equal(again[1], plain)
        assert [w for w, _ in staging_ledger] == ["get", "put"] * 2
        assert staging_ledger[2][1] == staging_ledger[0][1]
    run(body(), timeout=120)


def test_a_batch_that_fails_over_is_stacked_once(monkeypatch):
    async def body():
        impl = _impl(4, 2)
        svc = offload.get_service()
        svc.linger_ms = 20.0
        slots = svc._topology()
        assert len(slots) > 1
        rng = np.random.default_rng(9)
        a, b = (rng.integers(0, 256, (4, 4, C), dtype=np.uint8)
                for _ in range(2))
        plain = await asyncio.gather(svc.encode(impl, a),
                                     svc.encode(impl, b))
        key = ("enc", impl.coding_matrix.tobytes(), C)
        victim = slots[hash(key) % len(slots)]
        real = impl.encode_stripes

        def encode_stripes(batch):
            if victim.jdev in batch.devices():
                raise RuntimeError("chip down")     # after the H2D
            return real(batch)
        monkeypatch.setattr(impl, "encode_stripes", encode_stripes)
        base, ledger0 = dict(svc.stats), _copied("buffer_to_staging")
        got = await asyncio.gather(svc.encode(impl, a), svc.encode(impl, b))
        await svc.drain()
        assert all(np.array_equal(g, p) for g, p in zip(got, plain))
        assert svc.stats["device_failovers"] - base["device_failovers"] == 1
        assert _copied("buffer_to_staging") - ledger0 == a.nbytes + b.nbytes
    run(body(), timeout=120)


@pytest.mark.parametrize("S,n,c,first,count", [
    (128, 8, 4096, 0, 8), (128, 3, 4096, 0, 3), (4, 5, 64, 1, 3),
    (1, 8, 4096, 2, 4), (7, 11, 512, 10, 1), (3, 2, 16, 0, 0)])
def test_planes_from_stripes_is_the_strided_copy(S, n, c, first, count):
    """The native de-interleave (one call without the GIL for a run of
    shards) against numpy's own strided copy, plane by plane."""
    rng = np.random.default_rng(S * n + c)
    stripes = rng.integers(0, 256, (S, n, c), dtype=np.uint8)
    out = np.full(count * S * c, 0xA5, dtype=np.uint8)
    ec_native.planes_from_stripes(stripes, first, count, out)
    for p in range(count):
        assert np.array_equal(out[p * S * c:(p + 1) * S * c].reshape(S, c),
                              stripes[:, first + p, :])
    for bad in (lambda: ec_native.planes_from_stripes(
                    stripes, first, n - first + 1, out),
                lambda: ec_native.planes_from_stripes(
                    stripes[:, ::-1, :], first, count, out),
                lambda: ec_native.planes_from_stripes(
                    stripes, first, count, np.empty(out.size + 1, np.uint8))):
        with pytest.raises(ValueError):
            bad()


@pytest.mark.parametrize("want", [None, {0, 1, 2, 5, 6, 9, 10}, {3}, {8, 9},
                                  {0, 7, 10}])
def test_a_subset_of_shards_is_assembled_run_by_run(want, monkeypatch):
    """Neighbouring shards of one source are one copy; what comes out is
    what a copy a plane gives, for the shards asked and no others."""
    k, m, S = 8, 3, 16
    impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
    data, = _objects(k, S, 1, seed=11)
    stripes = np.frombuffer(data, dtype=np.uint8).reshape(S, k, C)
    parity = np.asarray(impl.encode_stripes(stripes))
    calls = []
    real = ec_native.planes_from_stripes

    def gather(src, first, count, out):
        calls.append((first + (k if src.shape[1] == m else 0), count))
        return real(src, first, count, out)
    monkeypatch.setattr(ec_native, "planes_from_stripes", gather)
    got = ec_util.encode(sinfo, impl, data, want)
    ids = sorted(want) if want is not None else list(range(k + m))
    assert list(got) == ids
    for i in ids:
        plane = stripes[:, i, :] if i < k else parity[:, i - k, :]
        assert bytes(got[i]) == plane.tobytes()
    runs = []
    for i in ids:
        if runs and i != k and runs[-1][0] + runs[-1][1] == i:
            runs[-1][1] += 1
        else:
            runs.append([i, 1])
    assert calls == [tuple(r) for r in runs]


def test_a_writes_planes_are_one_block_that_its_last_shard_frees():
    """The copied planes of a write share one allocation: any one of the
    shards keeps all of it (a sub-op queued to a slow replica holds its
    write's eleven planes, not its own), and nothing else does once the
    last shard is gone: not the service, its batch or the finisher."""
    import gc
    import weakref

    async def body():
        k, m, S = 8, 3, 16
        impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
        svc = offload.get_service()
        data, = _objects(k, S, 1)
        shards, _ = await ec_util.encode_csums_async(sinfo, impl, data, C,
                                                     service=svc)
        await svc.drain()
        blocks = {id(v.obj.base): v.obj.base for v in shards.values()}
        assert len(blocks) == 1
        (block,) = blocks.values()
        assert block.nbytes == (k + m) * S * C
        gone = weakref.ref(block)
        del blocks, block
        last = shards.pop(k + m - 1)
        shards.clear()
        gc.collect()
        assert gone() is not None and gone().nbytes == (k + m) * S * C
        del last
        gc.collect()
        assert gone() is None
    run(body(), timeout=120)


@pytest.mark.parametrize("k,m,S", SHAPES, ids=_ids)
def test_a_shards_checksums_come_with_it(k, m, S, monkeypatch):
    """`encode_csums_async`: each shard's crc32c by chunk, equal to the
    native kernel's over the shard, taken on the staging-pool thread
    through the service and on the caller's without one."""

    async def body():
        impl, sinfo = _impl(k, m), ec_util.StripeInfo(k, k * C)
        svc = offload.get_service()
        objects = _objects(k, S, 3)
        where = []
        real = ec_native.crc32c_blocks

        def crc32c_blocks(data, block, *a):
            where.append(threading.current_thread())
            return real(data, block, *a)
        monkeypatch.setattr(ec_native, "crc32c_blocks", crc32c_blocks)
        svc.linger_ms, svc.max_batch_bytes = 50.0, 64 << 20
        base = dict(svc.stats)
        served = await asyncio.gather(*[
            ec_util.encode_csums_async(sinfo, impl, o, C, service=svc)
            for o in objects])
        assert where and all(t.name.startswith("ec-offload") for t in where)
        assert svc.stats["jobs"] - base["jobs"] == 3       # no CrcJob
        del where[:]
        inline = [await ec_util.encode_csums_async(sinfo, impl, o, C)
                  for o in objects]
        assert where and all(t is threading.main_thread() for t in where)
        monkeypatch.setattr(ec_native, "crc32c_blocks", real)
        for (shards, csums), (shards0, csums0) in zip(served, inline):
            assert sorted(csums) == sorted(shards) == list(range(k + m))
            for i in shards:
                assert bytes(shards[i]) == bytes(shards0[i])
                assert csums[i] == csums0[i] == real(
                    np.frombuffer(shards[i], dtype=np.uint8), C).tolist()
                assert len(csums[i]) == S
        # and without a block, none
        shards, csums = await ec_util.encode_csums_async(
            sinfo, impl, objects[0], 0, service=svc)
        assert csums is None and sorted(shards) == list(range(k + m))
        assert await ec_util.encode_csums_async(
            sinfo, impl, b"", C, service=svc) == (
            {i: b"" for i in range(k + m)}, {i: [] for i in range(k + m)})
    run(body(), timeout=120)


def test_a_writes_checksums_ride_its_encode(tmp_path):
    """On a pool of the device plugin a write is one job of the offload
    service, its shards' checksums taken by the encode's finisher; with
    `ec_offload_crc_device` on they are a CrcJob of their own again.
    Either way every chunk read back passes its stored crc."""
    from tests.test_offload import ClusterHarness, _ec_tpu_cluster

    async def body():
        harness = ClusterHarness(tmp_path, n_osds=3)
        client, io = await _ec_tpu_cluster(harness)
        svc = offload.get_service()
        try:
            payloads = {f"o{n}": bytes([n + 1]) * (1 << 20)
                        for n in range(3)}
            base = dict(svc.stats)
            await asyncio.gather(*[io.write_full(n, p)
                                   for n, p in payloads.items()])
            await svc.drain()
            assert svc.stats["jobs"] - base["jobs"] == 3
            assert "host" not in svc.device_snapshot()
            for n, p in payloads.items():
                assert await io.read(n) == p
            svc.crc_device = True
            base = dict(svc.stats)
            await io.write_full("o9", bytes([9]) * (1 << 20))
            await svc.drain()
            assert svc.stats["jobs"] - base["jobs"] == 2
            assert svc.stats["crc_jobs"] - base["crc_jobs"] == 1
            assert await io.read("o9") == bytes([9]) * (1 << 20)
        finally:
            svc.crc_device = False
            await harness.stop()
    run(body(), timeout=120)
