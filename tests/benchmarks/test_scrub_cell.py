"""The scrub deployment (`radosbench_ec83_tpu_scrub`, cell
`rb4m_scrub_seqread`): the plain reference for deep scrub against the
program's digests, on the device and on the host, at batch sizes that
are padded and that are not; a rotted byte judged by both; the new
readers on hand-built spans and counters; the entries; and the cell
served tiny on the CPU backend."""
from __future__ import annotations

import asyncio
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, reference, reference_scrub  # noqa: E402
from tests.benchmarks.test_benchmarks import BENCH, _tiny  # noqa: E402

CELL = "rb4m_scrub_seqread"
CONFIG = "radosbench_ec83_tpu_scrub"
K, M, CHUNK = 8, 3, 4096
NEW = ["scrub_hashed_mib_s", "scrub_round_ms", "scrub_reserve_failed_pct",
       "crc_ops_per_batch", "crc_device_call_ms", "crc32c_blocks_roofline"]
#: what a round found and whose turn did not come, and three accepted
#: readers under names of this cell's own (their entries' `workloads`
#: are not this PR's to append to)
FOUND = ["scrub_errors_found", "scrub_pgs_without_round"]
#: accepted entries that list this cell since PR 41 (before it, readers
#: of this cell's own imported them as `<name>.scrub`); a third,
#: `offload_lane_busy_pct`, was retired in PR 44 (since PR 43 the lane's
#: wall time holds a batch's host copies and rises when the loop is
#: relieved: `loop_offload_pct` and `device_idle_pct` say what it said)
FOLDED = ["ec_read_ms", "loop_offload_pct"]
FROM_TRACE = {"device_idle_pct", "crc32c_blocks_roofline"}
HOPS = {"sem_wait_us": 100.0, "pool_wait_us": 200.0, "resume_us": 700.0,
        "h2d_submit_us": 1000.0, "launch_us": 2000.0,
        "result_wait_us": 500.0}
#: batch sizes: one block, a few, a program's own size, one past it,
#: and sizes that pad to 512 and 1024 rows
BLOCKS = [1, 5, 128, 129, 300, 1000]


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _offload_defaults():
    from ceph_tpu.offload import service

    return service._DEFAULTS


# -- the plain reference ---------------------------------------------------------

def test_reference_shares_nothing_with_the_program():
    src = open(os.path.join(ROOT, "benchmarks", "reference_scrub.py")).read()
    code = src.split('"""', 2)[2]
    assert "ceph_tpu" not in code and "jax" not in code
    assert "from benchmarks.reference import expected_shards" in code


def test_reference_crc_is_the_published_crc32c():
    """The check value of CRC-32C ("123456789" -> 0xE3069283) is stated
    with the final xor that `ceph_crc32c` leaves out."""
    assert reference_scrub.crc32c(b"123456789") ^ 0xFFFFFFFF == 0xE3069283
    assert reference_scrub.crc32c(b"") == 0xFFFFFFFF
    data = np.random.default_rng(2 ** 31 + 32).bytes(3 * 64)
    per_block = reference_scrub.block_digests(data, 64)
    assert per_block.dtype == np.uint32
    assert per_block.tolist() == [reference_scrub.crc32c(data[i:i + 64])
                                  for i in range(0, len(data), 64)]
    with pytest.raises(ValueError):
        reference_scrub.block_digests(data[:-1], 64)


def _seeded_blocks(n, block=CHUNK):
    return np.random.default_rng([2 ** 31 + 32, n, block]).integers(
        0, 256, (n, block), dtype=np.uint8)


@pytest.mark.parametrize("n", BLOCKS)
def test_program_digests_as_the_reference_does(n):
    """Device program and native host kernel against the bitwise
    definition, at sizes the device pads and sizes it does not."""
    from ceph_tpu.native import ec_native
    from ceph_tpu.ops import crc32c as crc_dev

    blocks = _seeded_blocks(n)
    want = reference_scrub.block_digests(blocks.reshape(-1), CHUNK)
    assert np.array_equal(ec_native.crc32c_blocks(blocks.reshape(-1), CHUNK),
                          want)
    dev = crc_dev.get_device_crc(CHUNK)
    got = dev(blocks)
    assert isinstance(got, np.ndarray) and got.shape == (n,)
    assert np.array_equal(got, want)
    rows = crc_dev.batch_rows(n)
    assert rows >= max(n, crc_dev.MIN_BATCH_BLOCKS) and rows & (rows - 1) == 0
    assert rows == crc_dev.MIN_BATCH_BLOCKS or rows < 2 * n


@pytest.mark.parametrize("block", [512, 4096])
def test_offload_service_pads_batches_and_drops_the_padding(block):
    """Through the served path with `crc_device` on: jobs of ragged
    sizes coalesce, each batch is staged at a power of two of rows, the
    padded rows' results reach nobody, and the counters and tags are
    the jobs' own."""
    from ceph_tpu import offload
    from ceph_tpu.ops import crc32c as crc_dev
    from ceph_tpu.utils import tracer

    sizes = [3, 128, 200, 77]

    async def body():
        svc = offload.get_service()
        svc.crc_device = True
        svc.prepare_crc(block)
        await svc.drain()
        before = dict(svc.stats)
        tracer.enable()
        cursor = tracer.collector().last_seq()
        jobs = [_seeded_blocks(n, block) for n in sizes]
        got = await asyncio.gather(*[svc.crc32c_blocks(j, block)
                                     for j in jobs])
        await svc.drain()
        spans = [s["tags"] for s in tracer.collector().spans()
                 if s["seq"] > cursor and s["name"] == "offload_batch"]
        tracer.disable()
        return svc, before, dict(svc.stats), got, jobs, spans
    svc, before, after, got, jobs, spans = asyncio.run(body())
    for j, g in zip(jobs, got):
        assert np.array_equal(g, reference_scrub.block_digests(
            j.reshape(-1), block))
    assert after["crc_jobs"] - before["crc_jobs"] == len(sizes)
    assert after["crc_bytes"] - before["crc_bytes"] == sum(sizes) * block
    assert after["fallback_ops"] == before["fallback_ops"]
    assert spans and {t["kind"] for t in spans} == {"crc"}
    assert sum(t["blocks"] for t in spans) == sum(sizes)
    assert len(spans) == after["crc_batches"] - before["crc_batches"]
    for t in spans:
        assert t["block_size"] == block
        assert t["padded_blocks"] == crc_dev.batch_rows(t["blocks"])
        assert t["bytes"] == t["blocks"] * block
        assert t["device"] != "host"


def test_programs_are_ready_before_a_batch_needs_them():
    """`prepare_crc` with `crc_device` on compiles every program a batch
    can need, so the batches that follow compile nothing, whatever
    their sizes; a job larger than a batch is split."""
    import jax

    from ceph_tpu import offload
    from ceph_tpu.ops import crc32c as crc_dev

    block = 1024
    compiles = []

    def on_compile(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    async def body():
        svc = offload.get_service()
        svc.crc_device = True
        svc.max_batch_bytes = 256 * block
        svc.prepare_crc(block)
        await svc.drain()
        programs = {rows for rows, _dev in
                    crc_dev.get_device_crc(block)._programs}
        assert programs == {128, 256, 512}     # up to 2 x max_batch_bytes
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            out = {}
            for n in (1, 130, 255, 256, 700):
                blocks = _seeded_blocks(n, block)
                out[n] = (blocks, await svc.crc32c_blocks(blocks, block))
            await svc.drain()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)
        return out
    out = asyncio.run(body())
    assert compiles == []
    for n, (blocks, got) in out.items():
        assert got.shape == (n,)
        assert np.array_equal(got, reference_scrub.block_digests(
            blocks.reshape(-1), block))


def test_scrub_map_and_verdict_name_the_rotted_shard():
    rng = np.random.default_rng(2 ** 31 + 33)
    objects = {"a": rng.bytes(3 * K * 1024 + 5), "b": rng.bytes(K * 1024)}
    smap = reference_scrub.scrub_map(objects, K, M, 1024)
    assert smap["a"]["size"] == 4 * 1024 and smap["b"]["size"] == 1024
    assert smap["a"]["digests"].shape == (K + M, 4)
    shards = reference.expected_shards(objects["a"], K, M, 1024)
    blobs = {j: shards[j].tobytes() for j in range(K + M)}
    assert reference_scrub.verdict(smap["a"], blobs, 1024) == []
    rotted = bytearray(blobs[9])
    rotted[2048 + 7] ^= 0x10
    blobs[9] = bytes(rotted)
    blobs[2] = blobs[2][:-1024]
    del blobs[5]
    assert reference_scrub.verdict(smap["a"], blobs, 1024) == [2, 5, 9]


def test_a_rotted_byte_gets_the_references_verdict_from_the_program(
        tmp_path):
    """One byte of one shard at rest rotted through the program's own
    fault path: the reference's verdict names the shard, the program's
    deep scrub (digests on the device) names the OSD that holds it and
    repairs it, and both then find the object clean."""
    from ceph_tpu.utils import flight
    from tests.test_ec_rmw import make_ec_cluster

    async def body():
        c, cl, io = await make_ec_cluster(tmp_path, 2, 1, 3, pg_num=4,
                                          plugin="tpu")
        try:
            for osd in c.osds.values():
                osd.config.set("ec_offload_crc_device", True)
            value = np.random.default_rng(2 ** 31 + 34).bytes(6 * 4096)
            await io.write_full("obj", value)
            want = reference_scrub.scrub_map({"obj": value}, 2, 1,
                                             4096)["obj"]
            osds = list(c.osds.values())
            assert reference_scrub.verdict(
                want, harness._shard_blobs(osds, "ecpool", "obj"), 4096) == []
            prim = next(pg for o in osds for pg in o.pgs.values()
                        if pg.pool.name == "ecpool" and pg.is_primary()
                        and "obj" in pg.list_objects())
            victim = next(o for o in osds if o.whoami != prim.host.whoami)
            assert "injected" in await victim._inject_bitrot("obj")
            (shard,) = reference_scrub.verdict(
                want, harness._shard_blobs(osds, "ecpool", "obj"), 4096)
            assert prim.acting[shard] == victim.whoami
            seq = flight.last_seq()
            res = await prim.scrub(deep=True)
            assert (res["errors"], res["repaired"]) == (1, 1)
            assert res["inconsistent"] == ["obj"]
            (found,) = [e for e in flight.events_since(seq)["events"]
                        if e["type"] == "scrub_mismatch"]
            assert found["detail"]["osds"] == [victim.whoami]
            # the push that repairs is applied when it arrives
            for _ in range(100):
                if not reference_scrub.verdict(
                        want, harness._shard_blobs(osds, "ecpool", "obj"),
                        4096):
                    break
                await asyncio.sleep(0.02)
            assert reference_scrub.verdict(
                want, harness._shard_blobs(osds, "ecpool", "obj"), 4096) == []
            res = await prim.scrub(deep=True)
            assert res["errors"] == 0 and await io.read("obj") == value
        finally:
            await c.stop()
    asyncio.run(asyncio.wait_for(body(), 60))


# -- the configuration, its traffic and the entries ----------------------------------

def test_configuration_is_the_siblings_pool_in_its_scrub_hours():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json")))
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "radosbench_ec83_tpu.json")))
    for key in ("pool", "osds", "object_size", "concurrent_ops",
                "generator", "offload_service", "hosts", "objectstore"):
        assert config[key] == sibling[key], key
    assert config["osd_config"] == {
        "osd_scrub_interval": 1.0, "osd_deep_scrub_every": 1,
        "ec_offload_crc_device": True, "osd_heartbeat_grace": 20.0}
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(
        list(sibling["reduced"]) + ["pg_bytes"])
    assert set(sibling["guarantees"]) < set(config["guarantees"])
    # every option the configuration sets or states is one the program has
    from ceph_tpu.osd.daemon import OSD

    declared = OSD(0, [("127.0.0.1", 1)]).config
    for key in list(config["osd_config"]) + [
            k for k in config["scrub"] if k != "note"]:
        declared.get(key)
    for key, value in config["scrub"].items():
        if key != "note":
            assert declared.get(key) == value, key
    sib_traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "rb4m_seqread.json")))
    assert traffic == sib_traffic
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200


def entries_stand(bench, root=ROOT):
    """PR 32 appended them after the messenger's send counters (PR 30);
    PR 41 took one of those and this cell's three renamed readers out,
    so the place is found by name; a later PR's come after, and a
    later cell may join a list."""
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(NEW[0])
    assert names[at - 1] == "msgr_sends_per_op"
    assert names[at:at + 8] == NEW + FOUND
    assert names[at + 8] == "store_write_direct_pct"
    for m in entries[at:at + 8]:
        assert CELL in m["workloads"]
        mod = _reader(m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == [
        "loop_offload_pct", "ec_read_ms"]
    assert "offload_lane_busy_pct" not in names
    with pytest.raises(SystemExit):
        _reader("offload_lane_busy_pct")
    assert [c["name"] for c in bench["configs"]][:3] == [
        "radosbench_ec83_tpu", "radosbench_ec83_tpu_degraded", CONFIG]
    assert [w["name"] for w in bench["workloads"]][:4] == [
        "rb4m_write", "rb4m_seqread", "rb4m_degraded_seqread", CELL]
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200
    loaded = {r.NAME for r in harness.load_cell(CELL, root=root).readers}
    assert set(NEW + FOUND + FOLDED) <= loaded


def test_the_entries_stand_in_their_order_after_what_stood_before_them():
    entries_stand(BENCH)


# -- the readers on hand-built spans and counters ------------------------------------

def _batch(kind=None, **tags):
    t = {**HOPS, "ops": 1, "bytes": 512 << 10, "device": "tpu:0", **tags}
    if kind is not None:
        t["kind"] = kind
    return {"name": "offload_batch", "duration_us": 5000.0, "tags": t}


def _round(state, us=400000.0, **tags):
    return {"name": "scrub_round", "duration_us": us,
            "tags": {"pgid": "1.0", "deep": True, "state": state, **tags}}


def _chunk(nbytes, blocks):
    return {"name": "scrub_chunk", "duration_us": 9000.0,
            "tags": {"objects": 4, "bytes": nbytes, "blocks": blocks}}


def _ctx(spans=None, offload=(None, None), trace=None, peaks=None,
         window_s=10.0):
    groups = [{}, {}]
    for side in (0, 1):
        if offload[side] is not None:
            groups[side]["offload"] = offload[side]
    return types.SimpleNamespace(
        spans=spans or {}, open=groups[0], close=groups[1], trace=trace,
        peaks=peaks, window_s=window_s,
        cell=types.SimpleNamespace(
            config={"pool": {"k": K, "m": M, "pg_num": 32}}))


PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
TRACE = {"programs": {"jit__crc_blocks_jit": 0.0004,
                      "jit__apply_bitmatrix_batched_jit": 0.002},
         "busy_s": 0.0024, "window_s": 10.0}
OLD_STATS = {"jobs": 9, "batches": 7, "dec_jobs": 0, "dec_batches": 0}


@pytest.mark.parametrize("name", NEW + FOUND)
@pytest.mark.parametrize("case", ["nothing", "other_batches",
                                  "parents_counters"])
def test_reader_finds_nothing_on_a_program_without_the_spans(name, case):
    """The driver runs the parent on this cell: it opens no
    `scrub_round` or `scrub_chunk`, finishes no round, so sends the
    device no crc batch inside a window, and its `svc.stats` has no
    `crc_*`."""
    ctx = {
        "nothing": _ctx(),
        "other_batches": _ctx(
            spans={"offload_batch": [_batch(), _batch("enc"),
                                     _batch("crc", device="host",
                                            blocks=128, block_size=4096)]},
            trace=TRACE, peaks=PEAKS),
        "parents_counters": _ctx(offload=(OLD_STATS, OLD_STATS),
                                 trace=TRACE, peaks=PEAKS),
    }[case]
    assert _reader(name).read(ctx) is None


def test_scrub_readers_read_rounds_and_chunks():
    rounds = [_round("done", 300000.0, errors=0, repaired=0),
              _round("done", 500000.0, pgid="1.7", errors=2, repaired=1),
              _round("done", 900000.0, errors=1, repaired=1),
              _round("reserve_failed", 40000.0, pgid="1.9", errors=0),
              _round("reserve_failed", 60000.0), _round("aborted", 5.0)]
    chunks = [_chunk(2 << 20, 512)] * 33 + [_chunk(0, 0)]
    ctx = _ctx(spans={"scrub_round": rounds, "scrub_chunk": chunks})
    assert _reader("scrub_round_ms").read(ctx) == pytest.approx(500.0)
    assert _reader("scrub_reserve_failed_pct").read(ctx) == \
        pytest.approx(100 * 2 / 6)
    assert _reader("scrub_hashed_mib_s").read(ctx) == pytest.approx(6.6)
    # what the rounds that ended found, and the PGs none of them names
    assert _reader("scrub_errors_found").read(ctx) == 3.0
    assert _reader("scrub_pgs_without_round").read(ctx) == 30.0
    untagged = _ctx(spans={"scrub_round": [_round("done")]})
    assert _reader("scrub_errors_found").read(untagged) is None
    assert _reader("scrub_pgs_without_round").read(untagged) == 31.0
    # rounds that all lost their reservation: a share, and no median
    lost = _ctx(spans={"scrub_round": rounds[3:5]})
    assert _reader("scrub_reserve_failed_pct").read(lost) == 100.0
    assert _reader("scrub_round_ms").read(lost) is None
    assert _reader("scrub_errors_found").read(lost) is None
    assert _reader("scrub_pgs_without_round").read(lost) is None


@pytest.mark.parametrize("name", FOLDED)
def test_an_accepted_entry_lists_this_cell(name):
    """`ec_read_ms` and `loop_offload_pct` list their cells. Until PR 41 this one reported them as `<name>.scrub`
    through a reader of its own that imported the accepted one; now the
    accepted entry names the cell, and neither that entry nor that file
    is left."""
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL in by[name]["workloads"]
    assert by[name]["workloads"][0] in ("rb4m_write", "rb4m_seqread")
    assert name + ".scrub" not in by
    with pytest.raises(SystemExit):
        _reader(name + ".scrub")
    assert name in {r.NAME for r in harness.load_cell(CELL).readers}
    ctx = _ctx()
    ctx.device_delta = lambda key: 0
    assert _reader(name).read(ctx) is None


def test_crc_readers_read_device_crc_batches_only():
    crc = [_batch("crc", blocks=512, block_size=4096, padded_blocks=512),
           _batch("crc", blocks=300, block_size=4096, padded_blocks=512,
                  launch_us=2600.0),
           _batch("crc", blocks=128, block_size=4096, padded_blocks=128,
                  launch_us=3000.0)]
    others = [_batch("enc", launch_us=90000.0),
              _batch("crc", device="host", blocks=128, block_size=4096,
                     launch_us=90000.0)]
    ctx = _ctx(spans={"offload_batch": crc + others},
               offload=(dict(OLD_STATS, crc_jobs=10, crc_batches=4,
                             crc_bytes=1),
                        dict(OLD_STATS, crc_jobs=43, crc_batches=10,
                             crc_bytes=2)),
               trace=TRACE, peaks=PEAKS)
    assert _reader("crc_device_call_ms").read(ctx) == pytest.approx(4.1)
    assert _reader("crc_ops_per_batch").read(ctx) == pytest.approx(5.5)
    still = _ctx(offload=(ctx.close["offload"], ctx.close["offload"]))
    assert _reader("crc_ops_per_batch").read(still) is None


def test_roofline_reckons_the_unpadded_blocks_at_the_larger_bound():
    mod = _reader("crc32c_blocks_roofline")
    least = mod.least_seconds(1, 4096, PEAKS)
    assert least["hbm"] == pytest.approx(5.006e-9, rel=1e-3)
    assert least["int8"] == pytest.approx(5.336e-9, rel=1e-3)
    crc = [_batch("crc", blocks=512, block_size=4096, padded_blocks=512),
           _batch("crc", blocks=300, block_size=4096, padded_blocks=512)]
    ctx = _ctx(spans={"offload_batch": crc}, trace=TRACE, peaks=PEAKS)
    want = 100 * 812 * least["int8"] / 0.0004
    assert mod.read(ctx) == pytest.approx(want)
    assert 0 < mod.read(ctx) < 100
    # no trace, no peaks, or a trace in which the program never ran
    assert mod.read(_ctx(spans={"offload_batch": crc}, peaks=PEAKS)) is None
    assert mod.read(_ctx(spans={"offload_batch": crc}, trace=TRACE)) is None
    idle = dict(TRACE, programs={"jit__apply_bitmatrix_batched_jit": 0.002})
    assert mod.read(_ctx(spans={"offload_batch": crc}, trace=idle,
                         peaks=PEAKS)) is None


# -- the cell served, tiny, on the CPU backend ------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run of the cell through `_tiny` (3 OSDs, k=2 m=1, so
    every PG spans every OSD as on the benchmark's pool). The window's
    spans and the daemons are caught on the way."""
    seen = {"spans": None, "osds": None}
    real_stop, real_spans = harness.stop_osds, harness.window_spans

    async def stop_osds(n, seed, osds, client):
        seen["osds"] = osds
        return await real_stop(n, seed, osds, client)

    def window_spans(spans):
        seen["spans"] = real_spans(spans)
        return seen["spans"]
    harness.stop_osds, harness.window_spans = stop_osds, window_spans
    kept = dict(_offload_defaults())
    try:
        done, cell = _tiny(CELL, trace=True, seconds=4.0,
                           tmp=tmp_path_factory.mktemp("served"))
    finally:
        harness.stop_osds, harness.window_spans = real_stop, real_spans
        _offload_defaults().update(kept)
    return done, cell, seen


def test_tiny_served_run_is_correct_and_finishes_rounds(served):
    done, cell, seen = served
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert all(value <= limit for _n, value, limit in done["checks"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} - FROM_TRACE
    assert set(NEW + FOUND + FOLDED) - FROM_TRACE <= set(m)
    assert m["scrub_errors_found"] == 0
    assert 0 <= m["scrub_pgs_without_round"] <= 4
    assert m["ec_read_ms"] > 0 and m["loop_offload_pct"] > 0
    assert "offload_lane_busy_pct" not in m
    assert m["compiles_in_window"] == 0
    assert done["info"]["compiles_in_window"] == 0
    assert m["scrub_hashed_mib_s"] > 0 and m["scrub_round_ms"] > 0
    assert 0 <= m["scrub_reserve_failed_pct"] < 100
    assert m["crc_ops_per_batch"] >= 1 and m["crc_device_call_ms"] > 0
    rounds = seen["spans"]["scrub_round"]
    done_rounds = [s["tags"] for s in rounds if s["tags"]["state"] == "done"]
    assert len(done_rounds) >= 4
    legs = {"reserve_us", "grant_wait_us", "scan_us", "digest_us",
            "compare_us"}
    for t in done_rounds:
        assert t["deep"] is True and legs <= set(t)
        assert t["errors"] == t["repaired"] == 0
        assert t["bytes"] == t["objects"] * 32768     # one shard each
    chunks = [s["tags"] for s in seen["spans"]["scrub_chunk"]]
    assert all(t["bytes"] == t["blocks"] * 4096 for t in chunks)
    # every member of a round's PG scans: three chunks a round that
    # had an object to scan (the empty PG's turn comes two or three
    # times), but two, which stand for the window's edges
    scanned = [t for t in done_rounds if t["objects"]]
    assert len(scanned) >= 4
    assert len(chunks) >= 3 * (len(scanned) - 2)


def test_tiny_served_run_digests_on_the_device_and_finds_nothing(served):
    done, _cell, seen = served
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["fallback_ops"] == checks["breaker_trips"] == \
        checks["device_failovers"] == checks["lanes_off_platform"] == 0
    batches = [s["tags"] for s in seen["spans"]["offload_batch"]]
    assert batches and {t["kind"] for t in batches} == {"crc"}
    assert all(t["device"] != "host" and t["padded_blocks"] >= t["blocks"]
               for t in batches)
    scrubbed = 0
    for osd in seen["osds"]:
        for pg in osd.pgs.values():
            if pg.pool.name == "bench" and pg.is_primary() \
                    and pg.last_scrub is not None:
                scrubbed += 1
                assert pg.last_scrub["errors"] == 0
                assert pg.last_scrub["repaired"] == 0
                assert not pg.inconsistent_objects
    assert scrubbed >= 4
