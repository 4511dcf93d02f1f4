"""The cell `rb64k_bluestore_write` (configuration
`radosbench_ec83_tpu_64k_on_bluestore`): its entries and files, the
plain reference of a deferred write (`benchmarks/reference_deferred.py`),
the six readers of the deferred path on hand-made contexts, and the cell
served tiny on the CPU backend at the pool's own shape (k=8 m=3 on eleven
OSDs, 64 KiB objects, so eleven 8 KiB deferred writes an op): correct,
nothing lost on a fresh mount, every staged byte acknowledged from the
KV's sync alone, and incorrect when a shard rots or a store is torn."""
from __future__ import annotations

import ast
import asyncio
import json
import os
import time
import types

import pytest

from tests.benchmarks.test_benchmarks import (BENCH, ROOT, _tiny,
                                              appended_copy)
from benchmarks import harness, reference_deferred

CONFIG = "radosbench_ec83_tpu_64k_on_bluestore"
CELL = "rb64k_bluestore_write"
SIBLING = "radosbench_ec83_tpu_64k"
#: name -> (unit, better, the end-to-end metric it should move)
NEW = {"bstore_deferred_bytes_pct": ("%", "higher", "op_p50_ms"),
       "bstore_deferred_lag_ms": ("ms", "lower", "op_p95_ms"),
       "bstore_deferred_pending_peak_kib": ("KiB", "lower", "op_p95_ms"),
       "bstore_deferred_ops_per_flush": ("ops/flush", "higher", "ops_s"),
       "kv_bytes_per_user_byte": ("B/B", "lower", "ops_s"),
       "kv_maintenance_ms_per_op": ("ms/op", "lower", "op_p95_ms")}
#: accepted readers that list their cells and do not list this one (the
#: next `benchmark` PR's to extend: PERF.md 7)
NOT_MINE = {"bstore_txcs_per_sync", "bstore_syncs_per_op",
            "bstore_commit_wait_ms", "bstore_acks_before_sync",
            "bstore_dev_bytes_per_user_byte", "enc_ops_per_batch",
            "enc_bitmatrix_roofline", "store_txns_per_op", "ec_encode_ms",
            "store_write_direct_pct", "store_read_direct_pct"}
K, M, CHUNK, SIZE, AU, LINE = 8, 3, 4096, 65536, 4096, 65536


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- BENCHMARK.json and the files it names --------------------------------------------

def entries_stand(bench, root=ROOT):
    """PR 53 appended one configuration, one cell and six per-layer
    entries after PR 52's. They are found by name; a later PR's come
    after, and a later cell may join a list."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index(CONFIG) == configs.index(SIBLING) + 1
    assert cells.index(CELL) == cells.index("rb64k_write") + 1
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index("bstore_deferred_bytes_pct")
    assert names[at - 1] == "msgr_rx_worker_busy_pct"
    assert names[at:at + 6] == list(NEW)
    for m in entries[at:at + 6]:
        unit, better, moves = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_span", "layer": "objectstore",
                     "moves": moves, "workloads": m["workloads"]}
        assert m["workloads"][0] == CELL
    assert "objectstore" in {m["layer"] for m in entries[:at]}
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == ["hosts", "object_count"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rb64k_write", 1)
    assert len(cell["why"]) <= 200
    # no accepted entry that lists cells was extended to this one
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == []
    loaded = [r.NAME for r in harness.load_cell(CELL, root=root).readers]
    unlisted = [m["name"] for m in entries[:at] if "workloads" not in m]
    assert loaded[:len(unlisted)] == unlisted
    assert loaded[len(unlisted):len(unlisted) + 6] == list(NEW)
    assert {"loop_busy_pct", "loop_store_pct", "loop_lag_p95_ms",
            "loop_gc_pct", "loop_cpu_ms_per_op", "device_idle_pct",
            "compiles_in_window", "msgr_frames_per_op",
            "osd_subop_ms_per_op"} <= set(loaded)
    assert not NOT_MINE & set(loaded)
    # every accepted cell loads the readers it loaded
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = {r.NAME for r in harness.load_cell(
                w["name"], root=root).readers}
            assert not other & set(NEW), w["name"]


def test_the_entries_stand_after_pr_52s_by_name():
    entries_stand(BENCH)
    for name, (unit, _better, moves) in NEW.items():
        mod = _reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, "objectstore", moves)


def test_the_entries_stand_with_a_later_prs_appended(tmp_path):
    root, bench = appended_copy(tmp_path)
    entries_stand(bench, root=root)


def test_the_configuration_is_the_64k_ones_on_bluestore():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    for word in ("obj_bencher.cc", "-b 65536 -t 16", "k=8 m=3", "BlueStore",
                 "hdd", "_do_alloc_write",
                 "bluestore_prefer_deferred_size_hdd 65536"):
        assert word in entry["source"], word
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", SIBLING + ".json")))
    blue = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs",
        "radosbench_ec83_tpu_on_bluestore.json")))
    assert body["name"] == CONFIG and body["source"] == entry["source"]
    # the 64 KiB configuration but for the keys ISSUE 53 names: no width
    # of the pool or of the object is changed
    differ = {key for key in set(body) | set(sibling)
              if body.get(key) != sibling.get(key)}
    assert differ == {"name", "source", "deployment", "objectstore",
                      "osd_config", "guarantees", "reduced", "assumed"}
    assert body["object_size"] == SIZE
    assert body["pool"] == {"type": "erasure", "plugin": "tpu", "k": K,
                            "m": M, "technique": "reed_sol_van",
                            "stripe_unit": CHUNK, "pg_num": 32}
    assert body["objectstore"] == "bluestore" and body["osds"] == 11
    assert body["osd_config"] == {
        **sibling["osd_config"], "bluestore_prefer_deferred_size": LINE}
    # the cut the MemStore configuration states is taken back
    assert "objectstore" in sibling["reduced"]
    assert sorted(body["reduced"]) == sorted(entry["reduced"]) == \
        ["hosts", "object_count"]
    assert body["reduced"] == blue["reduced"]
    # the guarantees of the BlueStore configuration, stated again
    g = body["guarantees"]
    assert set(g) == set(blue["guarantees"])
    for key in ("read", "shards_at_rest", "served_by"):
        assert g[key] == blue["guarantees"][key]
    assert "acknowledged only when all k+m shards are durable" \
        in g["durability"]
    assert "synced KV log" in g["durability"] \
        and "synced block file" in g["durability"]
    assert "read back from a fresh mount" in g["durability"]
    assert "replays" in g["durability"]
    assert "on_commit" in g["write_ack"]
    assert "no acknowledgement moves earlier than a sync that covers it" \
        in g["commit_order"]
    a = body["assumed"]
    for key in ("upstream_names", "flavour", "line", "min_alloc_size",
                "deferred_batch", "kv", "medium", "what_a_kill_is",
                "object_size", "osd_config"):
        assert key in a, key
    assert "from memory" in a["upstream_names"]
    for word in ("_deferred_queue", "_deferred_replay",
                 "bluestore_deferred_batch_ops_hdd",
                 "bluestore_throttle_deferred_bytes"):
        assert word in a["upstream_names"], word
    assert a["line"].startswith("strict") and a["min_alloc_size"].startswith(
        "4096")
    assert "RocksDB" in a["kv"] and "0.6 ms" in a["medium"]
    assert "no_deferred_write" not in a
    assert a["object_size"] == sibling["assumed"]["object_size"]


def test_the_line_the_configuration_states_is_the_programs():
    from ceph_tpu.objectstore import bluestore
    from ceph_tpu.osd.daemon import OSD

    assert bluestore.INLINE_MAX == LINE == bluestore.BlueStore(
        "/nonexistent").prefer_deferred_size
    assert bluestore.AU == AU
    osd = OSD(0, [("127.0.0.1", 1)])
    assert osd.config.get("bluestore_prefer_deferred_size") == LINE
    cell = harness.load_cell(CELL, root=ROOT)
    for key, value in cell.config["osd_config"].items():
        osd.config.set(key, value)      # the parent: ConfigError here
    a = cell.config["assumed"]["deferred_batch"]
    assert str(bluestore.DEFERRED_BATCH_OPS) in a and "128 MiB" in a
    assert bluestore.DEFERRED_MAX_BYTES == 128 << 20


def test_the_traffic_is_rb64k_writes_file_itself():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["traffic"] == cells["rb64k_write"]["traffic"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json"))
    cell = harness.load_cell(CELL, root=ROOT)
    assert cell.traffic == harness.load_cell("rb64k_write").traffic == {
        "op": "write", "clients": 16, "preload_objects": 0,
        "warmup_ops": 512, "payload_pool": 1024}
    assert callable(harness.store_factory(cell.config["objectstore"], []))


# -- the plain reference ---------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program_and_no_thread():
    path = os.path.join(ROOT, "benchmarks", "reference_deferred.py")
    tree = ast.parse(open(path).read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module)
    assert mods == {"__future__", "benchmarks"}
    names = {n.names[0].name for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.module == "benchmarks"}
    assert names == {"reference_bluestore"}


def test_the_reference_holds_64_kib_to_2_75_bytes_a_byte():
    got = reference_deferred.least_device_bytes(SIZE, K, M, CHUNK, AU, LINE)
    assert got == {"shard_bytes": 8192, "deferred": True,
                   "block_bytes": 90112, "kv_bytes": 90112,
                   "bytes_per_user_byte": 2.75,
                   "kv_bytes_per_user_byte": 1.375,
                   "syncs_before_ack": ("kv",)}
    assert _reader("kv_bytes_per_user_byte").least(
        harness.load_cell(CELL, root=ROOT).config) == 1.375


#: object bytes -> (shard bytes, units a shard, deferred), worked by
#: hand for k=8 m=3, chunk 4096, au 4096, line 65536: a stripe is 32,768
#: bytes, a shard a chunk a stripe
BY_HAND = {1: (4096, 1, True), 4095: (4096, 1, True), 4096: (4096, 1, True),
           65535: (8192, 2, True), 65536: (8192, 2, True),
           65537: (12288, 3, True),
           # 16 stripes: a shard of exactly the line is NOT deferred
           524288: (65536, 16, False), 524287: (65536, 16, False),
           491520: (61440, 15, True)}


@pytest.mark.parametrize("size", sorted(BY_HAND))
def test_least_device_bytes_by_hand(size):
    shard, n_units, deferred = BY_HAND[size]
    got = reference_deferred.least_device_bytes(size, K, M, CHUNK, AU, LINE)
    block = 11 * n_units * 4096
    assert got["shard_bytes"] == shard and got["deferred"] is deferred
    assert got["block_bytes"] == block
    assert got["kv_bytes"] == (block if deferred else 0)
    assert got["bytes_per_user_byte"] == \
        pytest.approx((2 if deferred else 1) * block / size)
    assert got["syncs_before_ack"] == (("kv",) if deferred
                                       else ("block", "kv"))
    # a shard that is no whole unit is padded on the device: au 8192
    odd = reference_deferred.least_device_bytes(size, K, M, CHUNK, 8192, LINE)
    assert odd["block_bytes"] == 11 * -(-shard // 8192) * 8192


@pytest.mark.parametrize("stage", reference_deferred.STAGES)
def test_the_references_device_at_each_stage(stage):
    """The model of the path: where a kill at `stage` leaves an 8 KiB
    write, and what the replay makes of it."""
    data, big = os.urandom(8192), os.urandom(LINE)
    dev = reference_deferred.Device(AU, LINE)
    dev.write_full("big", big)
    dev.write_full("a", data, upto=stage)
    want = reference_deferred.at_kill(stage)
    for keep in (False, True):
        killed = dev.kill(keep_unsynced=keep)
        assert bool(killed.kv["records"]) == want["record"]
        assert ("a" in killed.kv["objects"]) == want["acknowledged"]
        on_units = all(killed.block.get(u) is not None for u in (16, 17))
        assert on_units == (keep if want["on_units"] is None
                            else want["on_units"])
        assert killed.read("big") == big and not any(
            u in rec for rec in killed.kv["records"].values()
            for u in range(16))             # at the line: never a record
        found = killed.replay()
        assert found == int(want["record"])
        after = reference_deferred.after_replay(stage)
        assert killed.kv["records"] == {} and after["record"] is False
        assert killed.read("a") == (data if after["acknowledged"] else None)
        assert killed.replay() == 0         # twice: the same store
    txns = [[("mkcoll", 0)], [("write", 0, "a", 0, data)]]
    states = reference_deferred.states_after_kill(txns, 1, stage, 0)
    assert ({} in states) == (stage == "queued")


# -- the readers, on hand-made contexts ------------------------------------------------

def _txc(nbytes=8192, deferred=8192, **more):
    return {"name": "bstore_txc", "duration_us": 900.0,
            "tags": {"prepare_us": 200.0, "bytes": nbytes,
                     "deferred_bytes": deferred, "deferred_wait_us": 0.0,
                     **more}}


def _group(kv_bytes=9000, deferred_in=1, removed=0, **more):
    return {"name": "bstore_kv_sync", "duration_us": 700.0,
            "tags": {"txcs": 1, "kv_bytes": kv_bytes, "block_bytes": 0,
                     "deferred_in": deferred_in, "deferred_removed": removed,
                     **more}}


def _flush(ops=64, nbytes=64 * 8192, lag=300e3, pending=0):
    return {"name": "bstore_deferred_flush", "duration_us": 1500.0,
            "tags": {"ops": ops, "bytes": nbytes, "records": ops,
                     "write_us": 900.0, "sync_us": 600.0,
                     "oldest_lag_us": 2 * lag, "median_lag_us": lag,
                     "pending_bytes": pending}}


def _kv(name, us):
    return {"name": name, "duration_us": us,
            "tags": {"bytes_in": 4 << 20, "bytes_out": 1 << 20,
                     "entries": 900, "dropped": 5000}}


def _ctx(ops=10, written=10 * SIZE, **spans):
    cell = types.SimpleNamespace(config={
        "object_size": SIZE, "pool": {"k": K, "m": M, "stripe_unit": CHUNK},
        "osd_config": {"bluestore_prefer_deferred_size": LINE}})
    return types.SimpleNamespace(cell=cell, spans=spans, ops=ops,
                                 window_s=10.0,
                                 user_bytes={"write": written, "read": 0})


def _nothing_to_read():
    bare = {"tags": {}, "duration_us": 5.0}
    old_txc = {"name": "bstore_txc", "duration_us": 9.0,
               "tags": {"prepare_us": 1.0, "bytes": 524288}}
    old_group = {"name": "bstore_kv_sync", "duration_us": 9.0,
                 "tags": {"txcs": 2, "kv_bytes": 7000, "block_bytes": 1}}
    return {
        "nothing": _ctx(),
        # the parent's store: the two spans, without the path's tags
        "a_program_without_the_path": _ctx(bstore_txc=[old_txc],
                                           bstore_kv_sync=[old_group]),
        "spans_of_those_names_without_tags": _ctx(
            bstore_txc=[bare], bstore_kv_sync=[bare],
            bstore_deferred_flush=[bare], kv_flush=[bare], kv_compact=[bare]),
        "a_window_that_completed_nothing": _ctx(ops=0, written=0),
    }


@pytest.mark.parametrize("name", list(NEW))
@pytest.mark.parametrize("case", ["nothing", "a_program_without_the_path",
                                  "spans_of_those_names_without_tags",
                                  "a_window_that_completed_nothing"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """None, never 0 and never an exception: the line leaves the metric
    out, as the driver expects of a program without the path."""
    assert _reader(name).read(_nothing_to_read()[case]) is None


def test_deferred_share_is_of_the_staged_bytes():
    read = _reader("bstore_deferred_bytes_pct").read
    assert read(_ctx(bstore_txc=[_txc(), _txc(), _txc(0, 0)])) == 100.0
    # a 512 KiB extent beside three shards under the line
    assert read(_ctx(bstore_txc=[_txc(524288, 0), _txc(), _txc(),
                                 _txc()])) == pytest.approx(
        100 * 3 * 8192 / (524288 + 3 * 8192))
    assert read(_ctx(bstore_txc=[_txc(524288, 0)])) == 0.0
    assert read(_ctx(bstore_txc=[_txc(0, 0)])) is None   # no data staged


def test_the_flush_readers_on_three_batches():
    ctx = _ctx(bstore_deferred_flush=[
        _flush(64, 64 * 8192, 250e3, pending=3 * 8192),
        _flush(70, 70 * 8192, 400e3, pending=0),
        _flush(10, 10 * 8192, 90e3, pending=8192)])
    assert _reader("bstore_deferred_lag_ms").read(ctx) == pytest.approx(250.0)
    assert _reader("bstore_deferred_ops_per_flush").read(ctx) == \
        pytest.approx(48.0)
    assert _reader("bstore_deferred_pending_peak_kib").read(ctx) == \
        pytest.approx(70 * 8)


def test_kv_bytes_are_the_groups_over_the_user_bytes(capsys):
    groups = [_group(11 * 9000), _group(11 * 9000 + 4_000_000),
              _group(500, deferred_in=0, removed=64)]
    ctx = _ctx(ops=2, written=2 * SIZE, bstore_kv_sync=groups)
    got = _reader("kv_bytes_per_user_byte").read(ctx)
    assert got == pytest.approx((22 * 9000 + 4_000_500) / (2 * SIZE))
    err = capsys.readouterr().err
    assert err.startswith("benchmark: kv_bytes_per_user_byte = ")
    assert "reference_deferred least 1.375" in err
    assert got > 1.375


def test_maintenance_is_the_kvs_spans_over_the_ops():
    read = _reader("kv_maintenance_ms_per_op").read
    ctx = _ctx(ops=100, bstore_kv_sync=[_group()],
               kv_flush=[_kv("kv_flush", 9000.0), _kv("kv_flush", 11000.0)],
               kv_compact=[_kv("kv_compact", 80000.0)])
    assert read(ctx) == pytest.approx(1.0)
    # the path is there and nothing fell due: a reading, 0
    idle = read(_ctx(ops=100, bstore_kv_sync=[_group()]))
    assert idle == 0.0 and isinstance(idle, float)


# -- the cell, served tiny on the CPU backend -------------------------------------------

@pytest.fixture(scope="module")
def own_shape(tmp_path_factory):
    """The cell as it is served on the chip but for its length and its
    clients: k=8 m=3 on eleven OSDs, 64 KiB objects, four in flight,
    every `fsync` and `pwrite` of the process recorded. A batch of
    deferred writes is due at 16 extents here, so that a second's
    window sees some land."""
    from ceph_tpu.objectstore import bluestore
    from ceph_tpu.offload import service
    from tests.test_bluestore_commit import Syncs

    cell = harness.load_cell(CELL, root=ROOT)
    cell.config = dict(cell.config,
                       pool=dict(cell.config["pool"], pg_num=8))
    cell.traffic = dict(cell.traffic, clients=4, warmup_ops=8,
                        payload_pool=8)
    kept = dict(service._DEFAULTS)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bluestore, "DEFERRED_BATCH_OPS", 16)
        syncs = Syncs(patch)
        try:
            done = asyncio.run(harness.run_cell(
                cell, 2 ** 31 + 53, 1.2, True,
                str(tmp_path_factory.mktemp("own_shape")), time.monotonic(),
                ()))
        finally:
            service._DEFAULTS.update(kept)
    return done, syncs.log


def test_the_cell_at_the_pools_own_shape_is_correct_and_loses_nothing(
        own_shape):
    done, _log = own_shape
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert all(value == 0 == limit for _n, value, limit in done["checks"])
    assert {n for n, _v, _l in done["checks"]} >= {
        "shard_bytes_lost_on_remount", "shard_bytes_differing",
        "sample_read_mismatches", "fallback_ops",
        "encode_bytes_not_on_device", "osd_markdowns_under_load"}
    assert done["info"]["compiles_in_window"] == 0
    assert done["info"]["store_dir_bytes"] > 0
    assert done["info"]["failures"] == []


def test_the_traced_line_carries_the_six_and_every_byte_was_deferred(
        own_shape):
    done, _log = own_shape
    metrics = done["result"]["metrics"]
    assert set(NEW) <= set(metrics)
    for name, (unit, _better, _moves) in NEW.items():
        assert metrics[name]["unit"] == unit
    assert metrics["bstore_deferred_bytes_pct"]["value"] == 100.0
    assert metrics["bstore_deferred_lag_ms"]["value"] > 0.0
    assert metrics["bstore_deferred_ops_per_flush"]["value"] >= 16
    # 16 extents of two units and whatever queued behind them
    assert metrics["bstore_deferred_pending_peak_kib"]["value"] >= 16 * 8
    least = reference_deferred.least_device_bytes(
        SIZE, K, M, CHUNK, AU, LINE)["kv_bytes_per_user_byte"]
    assert least < metrics["kv_bytes_per_user_byte"]["value"] < 2 * least
    assert metrics["kv_maintenance_ms_per_op"]["value"] >= 0.0
    assert not NOT_MINE & set(metrics)
    assert 0.0 < metrics["loop_store_pct"]["value"] < 100.0


def test_every_sync_and_block_write_is_a_commit_threads(own_shape):
    import threading

    _done, log = own_shape
    mine = [(name, thread, os.path.basename(path))
            for name, edge, thread, path in log
            if edge == "end" and "/osd" in path
            and os.path.basename(path) in ("block", "wal.log")]
    assert {"fsync", "fdatasync", "pwrite"} <= {n for n, _t, _f in mine}
    assert threading.main_thread().ident not in {t for _n, t, _f in mine}
    # the shards land in batches: far fewer syncs of a block file than
    # writes to it
    writes = sum(n == "pwrite" for n, _t, _f in mine)
    syncs = sum(n == "fdatasync" for n, _t, _f in mine)
    assert writes >= 8 * syncs > 0


def test_tiny_traced_run_has_neither_of_the_stores_shares(tmp_path):
    """What `test_store_direct.py`'s generated case meant for this cell
    (it is marked in tests/conftest.py: it wants exactly one of two
    accepted lists to name every cell): a correct tiny traced run,
    neither share on its line."""
    done, cell = _tiny(CELL, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert cell.config["object_size"] == SIZE    # `shrink` keeps the size
    assert not {"store_write_direct_pct", "store_read_direct_pct"} \
        & set(line["metrics"])
    assert line["metrics"]["bstore_deferred_bytes_pct"]["value"] == 100.0
    assert all(value <= limit for _n, value, limit in done["checks"])


def test_tiny_untraced_run_reports_the_four_end_to_end_metrics(tmp_path):
    done, _cell = _tiny(CELL, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ops_s", "op_p50_ms", "op_p95_ms",
                                    "setup_s"}
    assert done["info"]["store_dir_bytes"] > 0


def test_a_torn_store_makes_the_run_incorrect(tmp_path):
    done, _cell = _tiny(CELL, control=("torn_store",), tmp=tmp_path)
    assert done["result"]["correct"] is False
    rows = {n: v for n, v, _l in done["checks"]}
    assert rows["shard_bytes_lost_on_remount"] > 0
    assert rows["shard_bytes_differing"] == 0       # live, it was whole


def test_a_rotten_shard_makes_the_run_incorrect(tmp_path):
    done, _cell = _tiny(CELL, control=("bitrot",), tmp=tmp_path)
    assert done["result"]["correct"] is False
    rows = {n: v for n, v, _l in done["checks"]}
    assert rows["shard_bytes_differing"] > 0
    assert rows["ops_failed"] == 0
