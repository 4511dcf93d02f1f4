"""The cell `rb4m_bluestore_write` (configuration
`radosbench_ec83_tpu_on_bluestore`): its entries, files and readers, the
plain reference's independence, and the cell served tiny on the CPU
backend, where it must be correct, lose nothing on a fresh mount, sync
beside the loop and never on it, and come out incorrect when a store is
torn."""
from __future__ import annotations

import ast
import json
import os
import threading
import types

import pytest

from tests.benchmarks.test_benchmarks import BENCH, ROOT, _tiny
from benchmarks import harness

CONFIG = "radosbench_ec83_tpu_on_bluestore"
CELL = "rb4m_bluestore_write"
#: name -> (unit, better, the end-to-end metric it should move)
NEW = {"bstore_txcs_per_sync": ("txcs/sync", "higher", "ops_s"),
       "bstore_syncs_per_op": ("fsyncs/op", "lower", "op_p50_ms"),
       "bstore_commit_wait_ms": ("ms", "lower", "op_p50_ms"),
       "bstore_sync_ms": ("ms", "lower", "op_p50_ms"),
       "bstore_prepare_ms_per_op": ("ms/op", "lower", "ops_s"),
       "bstore_dev_bytes_per_user_byte": ("B/B", "lower", "ops_s"),
       "bstore_acks_before_sync": ("count", "lower", "op_p95_ms")}
#: names the accepted tests append to a copy of the benchmark
#: (tests/benchmarks/test_benchmarks.py `appended_copy`): a real entry
#: under one of them would collide there
TAKEN = {"radosbench_ec83_tpu_bluestore", "rb4m_restart_write",
         "restart_ops", "store_fsyncs_per_op"}


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- BENCHMARK.json and the files it names --------------------------------------------

def entries_stand(bench, root=ROOT):
    """PR 45 appended one configuration, one cell and seven per-layer
    entries after the recovery cell's. They are found by name; a later
    PR's come after, and a later cell may join a list."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index(CONFIG) == \
        configs.index("radosbench_ec83_tpu_recovery") + 1
    assert cells.index(CELL) == cells.index("rb4m_recovery_write") + 1
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index("bstore_txcs_per_sync")
    assert names[at - 1] == "osd_recovery_ms_per_op"
    assert names[at:at + 7] == list(NEW)
    for m in entries[at:at + 7]:
        unit, better, moves = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_span", "layer": "objectstore",
                     "moves": moves, "workloads": m["workloads"]}
        assert CELL in m["workloads"]
    assert "objectstore" in {m["layer"] for m in entries[:at]}
    assert not TAKEN & set(configs + cells + names)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == ["hosts", "object_count"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rb4m_write", 1)
    assert len(cell["why"]) <= 200
    # the accepted entries that list cells by name do not list this one
    # before its own: it loads those without a list, and its seven
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == []
    loaded = [r.NAME for r in harness.load_cell(CELL, root=root).readers]
    unlisted = [m["name"] for m in entries[:at] if "workloads" not in m]
    assert loaded[:len(unlisted)] == unlisted
    assert set(NEW) <= set(loaded)
    assert {"loop_store_pct", "loop_lag_p95_ms", "loop_cpu_ms_per_op",
            "device_idle_pct", "compiles_in_window",
            "store_bytes_per_user_byte", "msgr_frames_per_op",
            "osd_subop_ms_per_op"} <= set(loaded)
    assert not {"ec_encode_ms", "store_commit_ms", "store_write_direct_pct",
                "store_read_direct_pct", "offload_handoff_ms",
                "apply_bitmatrix_batched_roofline"} & set(loaded)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = {r.NAME for r in harness.load_cell(
                w["name"], root=root).readers}
            assert not other & set(NEW), w["name"]


def test_the_entries_stand_after_the_recovery_cells_by_name():
    entries_stand(BENCH)
    for name, (unit, _better, moves) in NEW.items():
        mod = _reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, "objectstore", moves)


def test_the_configuration_is_the_north_stars_on_bluestore():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    for word in ("obj_bencher.cc", "-b 4194304 -t 16", "k=8 m=3",
                 "osd_objectstore", "BlueStore.cc", "_kv_sync_thread"):
        assert word in entry["source"], word
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "radosbench_ec83_tpu.json")))
    assert body["name"] == CONFIG and body["source"] == entry["source"]
    assert body["architecture"] is None     # a system that runs no model
    for key in ("generator", "object_size", "concurrent_ops", "pool", "osds",
                "offload_service", "osd_config", "hosts", "object_count"):
        assert body[key] == sibling[key], key
    assert body["objectstore"] == "bluestore" != sibling["objectstore"]
    # the cut the five MemStore configurations state is taken back
    assert "objectstore" in sibling["reduced"]
    assert sorted(body["reduced"]) == sorted(entry["reduced"]) == \
        ["hosts", "object_count"]
    assert body["reduced"]["hosts"].startswith(sibling["reduced"]["hosts"])
    assert "eleven directories" in body["reduced"]["hosts"]
    assert "thread of its own" in body["reduced"]["hosts"]
    assert body["reduced"]["object_count"] == \
        sibling["reduced"]["object_count"]
    g = body["guarantees"]
    assert set(sibling["guarantees"]) < set(g)
    assert sibling["guarantees"]["durability"].startswith("none")
    assert "acknowledged only when all k+m shards are durable" \
        in g["durability"]
    assert "read back from a fresh mount" in g["durability"]
    assert "on_commit" in g["write_ack"]
    for key in ("read", "shards_at_rest", "served_by"):
        assert g[key] == sibling["guarantees"][key]
    for key in ("min_alloc_size", "csum", "kv", "no_compression",
                "no_deferred_write", "freelist", "onode", "group_commit",
                "what_a_kill_is", "osd_config"):
        assert key in body["assumed"], key
    assert body["assumed"]["min_alloc_size"].startswith("4096")
    assert "crc32c at 4 KiB" in body["assumed"]["csum"]
    assert "RocksDB" in body["assumed"]["kv"]
    # the traffic is the write cell's own file, not a copy
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["traffic"] == cells["rb4m_write"]["traffic"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json"))
    cell = harness.load_cell(CELL)
    assert cell.traffic == harness.load_cell("rb4m_write").traffic
    assert callable(harness.store_factory(cell.config["objectstore"], []))


def test_the_reference_imports_nothing_of_the_program_and_no_thread():
    path = os.path.join(ROOT, "benchmarks", "reference_bluestore.py")
    tree = ast.parse(open(path).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "copy", "random"}


# -- the readers, on hand-built spans ---------------------------------------------------

def _txc(prepare=1000.0, queued=200.0, block=3000.0, kv=2000.0,
         deliver=300.0, ran_ahead=False, group=1, nbytes=524288):
    legs = {"prepare_us": prepare, "queued_us": queued,
            "block_sync_us": block, "kv_submit_us": kv,
            "deliver_us": deliver}
    return {"name": "bstore_txc", "start": 100.0,
            "duration_us": sum(legs.values()),
            "tags": {**legs, "ops": 4, "bytes": nbytes, "group": group,
                     "ran_ahead": ran_ahead}}


def _group(txcs, block_synced=1, kv_fsyncs=1, block_bytes=0, kv_bytes=0,
           freelist=0, duration=5000.0, group=1):
    return {"name": "bstore_kv_sync", "start": 100.0,
            "duration_us": duration,
            "tags": {"group": group, "txcs": txcs,
                     "block_synced": block_synced, "kv_fsyncs": kv_fsyncs,
                     "block_bytes": block_bytes, "kv_bytes": kv_bytes,
                     "freelist_bytes": freelist}}


def _ctx(ops=2, written=2 * 4194304, **by):
    return types.SimpleNamespace(spans=by, ops=ops, window_s=10.0,
                                 user_bytes={"write": written, "read": 0})


def _read_all(ctx):
    return {name: _reader(name).read(ctx) for name in NEW}


def test_readers_find_nothing_on_a_program_without_the_spans():
    """The parent's store opens neither span; a span of that name
    without the tags (another program's) is no reading either: None,
    never 0."""
    bare = {"name": "bstore_txc", "start": 1.0, "duration_us": 5.0,
            "tags": {}}
    other = {"name": "store_commit", "start": 1.0, "duration_us": 28.0,
             "tags": {"ops": 3}}
    for ctx in (_ctx(), _ctx(store_commit=[other]),
                _ctx(bstore_txc=[bare], bstore_kv_sync=[dict(
                    bare, name="bstore_kv_sync")]),
                _ctx(ops=0, written=0)):
        assert _read_all(ctx) == dict.fromkeys(NEW), ctx.spans


def test_one_group_of_three():
    """Three contexts share one sync of the block file and one of the
    log: 2 syncs for the one op, each context's wait its queue time,
    the group's two syncs and the way back."""
    txcs = [_txc(prepare=900.0), _txc(prepare=1200.0, queued=500.0),
            _txc(prepare=300.0, nbytes=0)]
    group = _group(3, block_bytes=2 * 524288, kv_bytes=6000 + 1000,
                   freelist=1000, duration=5000.0)
    got = _read_all(_ctx(ops=1, written=1048576, bstore_txc=txcs,
                         bstore_kv_sync=[group]))
    assert got["bstore_txcs_per_sync"] == 3.0
    assert got["bstore_syncs_per_op"] == 2.0
    assert got["bstore_commit_wait_ms"] == pytest.approx(5.5)   # median
    assert got["bstore_sync_ms"] == pytest.approx(5.0)
    assert got["bstore_prepare_ms_per_op"] == pytest.approx(2.4)
    assert got["bstore_dev_bytes_per_user_byte"] == \
        pytest.approx((1048576 + 7000) / 1048576)
    assert got["bstore_acks_before_sync"] == 0.0


def test_two_stores_and_a_delivery_that_ran_ahead():
    """Groups of two stores are summed, not told apart: one with a block
    sync, one of log entries alone whose submit flushed the memtable
    (four syncs); a context delivered before its group had finished is
    counted."""
    txcs = [_txc(group=1), _txc(group=1), _txc(group=7, block=0.0,
                                               nbytes=0),
            _txc(group=7, block=0.0, nbytes=0, ran_ahead=True)]
    groups = [_group(2, block_bytes=1048576, kv_bytes=4000,
                     duration=6000.0, group=1),
              _group(2, block_synced=0, kv_fsyncs=4, kv_bytes=5_000_000,
                     duration=9000.0, group=7)]
    got = _read_all(_ctx(ops=4, written=4 * 4194304, bstore_txc=txcs,
                         bstore_kv_sync=groups))
    assert got["bstore_txcs_per_sync"] == 2.0
    assert got["bstore_syncs_per_op"] == pytest.approx((1 + 1 + 0 + 4) / 4)
    assert got["bstore_sync_ms"] == pytest.approx(7.5)
    assert got["bstore_commit_wait_ms"] == pytest.approx(
        (5.5 + 2.5) / 2)
    assert got["bstore_prepare_ms_per_op"] == pytest.approx(1.0)
    assert got["bstore_dev_bytes_per_user_byte"] == pytest.approx(
        (1048576 + 4000 + 5_000_000) / (4 * 4194304))
    assert got["bstore_acks_before_sync"] == 1.0


# -- the cell, tiny, on the CPU backend ---------------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One tiny traced run, with every `fsync` and `fdatasync` of the
    process recorded beside the thread that made it."""
    from tests.test_bluestore_commit import Syncs

    with pytest.MonkeyPatch.context() as patch:
        syncs = Syncs(patch)
        done, cell = _tiny(CELL, trace=True,
                           tmp=tmp_path_factory.mktemp("bluestore"))
    return done, cell, syncs.log


def test_tiny_traced_run_is_correct_and_loses_nothing_on_remount(traced):
    done, cell, _made = traced
    assert cell.config["objectstore"] == "bluestore"
    line = done["result"]
    assert line["correct"] is True, done["checks"]
    checks = {name: (value, limit) for name, value, limit in done["checks"]}
    assert checks["shard_bytes_lost_on_remount"] == (0, 0)
    assert checks["shard_bytes_differing"] == (0, 0)
    assert all(value <= limit for value, limit in checks.values())
    assert done["info"]["compiles_in_window"] == 0
    assert done["info"]["store_dir_bytes"] > 0
    assert done["info"]["failures"] == []


def test_tiny_traced_run_reports_the_seven_and_neither_stores_share(traced):
    """What tests/benchmarks/test_store_direct.py's case for this cell
    meant: a correct tiny traced run with neither of the stores' two
    shares on its line (the entries list their own cells, and BlueStore
    keeps no body by reference)."""
    done, cell, _made = traced
    got = done["result"]["metrics"]
    declared = {r.NAME for r in cell.readers}
    assert set(got) == declared - {"device_idle_pct"}   # the TPU's alone
    assert not {"store_write_direct_pct", "store_read_direct_pct",
                "store_commit_ms", "ec_encode_ms"} & set(got)
    for name, (unit, _better, _moves) in NEW.items():
        assert got[name]["unit"] == unit
    assert got["bstore_acks_before_sync"]["value"] == 0.0
    assert got["bstore_txcs_per_sync"]["value"] >= 1.0
    assert got["bstore_syncs_per_op"]["value"] > 0.0
    assert got["bstore_commit_wait_ms"]["value"] > 0.0
    assert got["bstore_sync_ms"]["value"] > 0.0
    assert got["bstore_prepare_ms_per_op"]["value"] > 0.0
    assert got["bstore_dev_bytes_per_user_byte"]["value"] >= 1.5    # k=2 m=1
    assert got["store_bytes_per_user_byte"]["value"] == 0.0
    assert 0.0 < got["loop_store_pct"]["value"] < 100.0


def test_every_sync_of_a_store_is_made_beside_the_loop(traced):
    """No `fsync` inside a `store_commit` span: the block file's and
    the KV's syncs are all a commit thread's, none the event loop's."""
    _done, _cell, made = traced
    stores = [(name, thread) for name, edge, thread, path in made
              if edge == "end" and "/osd" in path
              and os.path.basename(path) in ("block", "wal.log")]
    # (32 KiB shards live in their onodes here: the log is all that
    # syncs; the 512 KiB extents of the chip's size sync the block file
    # too, which tests/test_bluestore_commit.py holds)
    assert "fsync" in {name for name, _t in stores}
    assert threading.main_thread().ident not in {t for _n, t in stores}


def test_tiny_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    done, _cell = _tiny(CELL, tmp=tmp_path)
    assert done["result"]["correct"] is True, done["checks"]
    assert set(done["result"]["metrics"]) == {"ops_s", "op_p50_ms",
                                              "op_p95_ms", "setup_s"}
    # the run removed the eleven (here three) directories it made
    assert done["info"]["store_dir_bytes"] > 0


def test_a_torn_store_fails_the_run(tmp_path):
    done, _cell = _tiny(CELL, control=("torn_store",), tmp=tmp_path)
    assert done["result"]["correct"] is False
    checks = {name: value for name, value, _limit in done["checks"]}
    assert checks["shard_bytes_lost_on_remount"] > 0
    assert checks["shard_bytes_differing"] == 0     # live, it was whole
