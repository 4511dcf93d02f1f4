"""The cell `rb64k_write` (configuration `radosbench_ec83_tpu_64k`):
its entries and files, the plain reference of a small whole-object write
(`benchmarks/reference_small.py`), the seven `enc_*` readers on
hand-made contexts, and the cell served tiny on the CPU backend: at the
pool's own shape (k=8 m=3 on eleven OSDs, 64 KiB objects) it must be
correct with every check row 0, hold the stores and the link to the
reference's 1.375 bytes a user byte, and come out incorrect when a
shard rots."""
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
from benchmarks import harness, reference, reference_small
from benchmarks.layer_metrics import apply_bitmatrix_batched_roofline

CONFIG = "radosbench_ec83_tpu_64k"
CELL = "rb64k_write"
SIBLING = "radosbench_ec83_tpu"
#: name -> (unit, better, source, layer, the end-to-end metric it moves)
NEW = {
    "enc_ops_per_batch": ("ops/batch", "higher", "program_counter",
                          "offload/service", "ops_s"),
    "enc_linger_flush_pct": ("%", "lower", "program_counter",
                             "offload/service", "op_p50_ms"),
    "enc_queue_wait_ms": ("ms", "lower", "program_span",
                          "offload/service", "op_p50_ms"),
    "enc_handoff_ms": ("ms", "lower", "program_span",
                       "offload/service", "op_p50_ms"),
    "enc_device_call_ms": ("ms", "lower", "program_span",
                           "H2D/D2H link", "op_p50_ms"),
    "enc_link_bytes_per_byte": ("B/B", "lower", "program_counter",
                                "H2D/D2H link", "ops_s"),
    "enc_bitmatrix_roofline": ("%", "higher", "device_trace",
                               "ops/rs_codec kernel", "ops_s"),
}
#: the accepted readers that list cells and do not list this one
NOT_MINE = {"ec_encode_ms", "offload_ops_per_batch", "link_bytes_per_byte",
            "apply_bitmatrix_batched_roofline", "store_commit_ms",
            "loop_offload_pct", "offload_handoff_ms",
            "offload_device_call_ms", "store_write_direct_pct",
            "store_read_direct_pct"}
K, M, CHUNK, SIZE = 8, 3, 4096, 65536
SIZES = [1, 4095, 4096, 32768, 65535, 65536, 65537]


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- BENCHMARK.json and the files it names --------------------------------------------

def entries_stand(bench, root=ROOT):
    """PR 49 appended one configuration, one cell and seven per-layer
    entries after the BlueStore cell's. They are found by name; a later
    PR's come after, and a later cell may join a list."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index(CONFIG) == \
        configs.index("radosbench_ec83_tpu_on_bluestore") + 1
    assert cells.index(CELL) == cells.index("rb4m_bluestore_write") + 1
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index("enc_ops_per_batch")
    assert names[at - 1] == "bstore_csum_reused_pct"
    assert names[at:at + 7] == list(NEW)
    layers = {m["layer"] for m in entries[:at]}
    for m in entries[at:at + 7]:
        unit, better, source, layer, moves = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": m["workloads"]}
        assert m["workloads"][0] == CELL
        assert layer in layers      # a layer the benchmark already names
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == ["hosts", "object_count",
                                        "objectstore"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200
    # no accepted entry that lists cells was extended to this one
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == []
    loaded = [r.NAME for r in harness.load_cell(CELL, root=root).readers]
    unlisted = [m["name"] for m in entries[:at] if "workloads" not in m]
    assert loaded[:len(unlisted)] == unlisted
    assert loaded[len(unlisted):len(unlisted) + 7] == list(NEW)
    assert {"loop_busy_pct", "loop_msgr_pct", "loop_osd_pct",
            "loop_cpu_ms_per_op", "msgr_frames_per_op", "msgr_rx_direct_pct",
            "msgr_tx_direct_pct", "osd_subop_ms_per_op", "queue_wait_pct",
            "store_bytes_per_user_byte", "device_idle_pct",
            "compiles_in_window"} <= set(loaded)
    assert not NOT_MINE & set(loaded)
    # every accepted cell loads the readers it loaded
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = {r.NAME for r in harness.load_cell(
                w["name"], root=root).readers}
            assert not other & set(NEW), w["name"]


def test_the_entries_stand_after_the_bluestore_cells_by_name():
    entries_stand(BENCH)
    for name, (unit, _better, _source, layer, moves) in NEW.items():
        mod = _reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, layer, moves)


def test_the_entries_stand_with_a_later_prs_appended(tmp_path):
    root, bench = appended_copy(tmp_path)
    entries_stand(bench, root=root)


def test_the_configuration_is_the_north_stars_at_64_kib():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    for word in ("obj_bencher.cc", "rados.cc", "doc/man/8/rados.rst",
                 "-b 65536 -t 16", "k=8 m=3"):
        assert word in entry["source"], word
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", SIBLING + ".json")))
    assert body["name"] == CONFIG
    for word in ("obj_bencher.cc", "rados.cc", "doc/man/8/rados.rst",
                 "-b 65536 -t 16"):
        assert word in body["source"], word
    assert body["object_size"] == SIZE != sibling["object_size"]
    # no width of the pool is changed, and nothing else of the
    # deployment; the guarantees stand word for word
    differ = {key for key in set(body) | set(sibling)
              if body.get(key) != sibling.get(key)}
    assert differ == {"name", "source", "deployment", "object_size",
                      "assumed"}
    assert body["guarantees"] == sibling["guarantees"]
    assert body["pool"] == {"type": "erasure", "plugin": "tpu", "k": K,
                            "m": M, "technique": "reed_sol_van",
                            "stripe_unit": CHUNK, "pg_num": 32}
    assert body["osds"] == 11 and body["objectstore"] == "memstore"
    assert sorted(body["reduced"]) == sorted(entry["reduced"])
    assert set(body["assumed"]) == \
        (set(sibling["assumed"]) - {"seq_wraps"}) | {"object_size"}
    for key in set(sibling["assumed"]) - {"seq_wraps"}:
        assert body["assumed"][key] == sibling["assumed"][key]
    why = body["assumed"]["object_size"]
    for word in ("65536", "this repository's", "two whole stripes", "1.375",
                 "8 KiB", "SPILL_SIZE", "encode_parts", "unzeroed",
                 "INLINE_MAX"):
        assert word in why, word


def test_the_line_the_configuration_names_is_the_programs():
    from ceph_tpu.msg import transport
    from ceph_tpu.objectstore import bluestore

    assert transport.SPILL_SIZE == bluestore.INLINE_MAX == SIZE
    lay = reference_small.layout(SIZE, K, M, CHUNK)
    assert lay["shard_bytes"] < transport.SPILL_SIZE <= SIZE


def test_the_traffic_is_the_issues_to_the_letter():
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json")))
    assert traffic == {"op": "write", "clients": 16, "preload_objects": 0,
                       "warmup_ops": 512, "payload_pool": 1024}
    cell = harness.load_cell(CELL, root=ROOT)
    assert cell.traffic == traffic and cell.config["concurrent_ops"] == 16
    # what set-up warms: every number of jobs a batch of this cell can
    # hold, sixteen of two stripes, far below `max_batch_bytes`
    assert (8 << 20) // SIZE > traffic["clients"]


# -- the plain reference ---------------------------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmarks", "reference_small.py")
    tree = ast.parse(open(path).read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module)
    assert mods == {"__future__", "numpy", "benchmarks"}


@pytest.mark.parametrize("size", SIZES)
def test_the_reference_stripes_a_small_object(size):
    lay = reference_small.layout(size, K, M, CHUNK)
    width = K * CHUNK
    assert lay["stripes"] == {1: 1, 4095: 1, 4096: 1, 32768: 1, 65535: 2,
                              65536: 2, 65537: 3}[size]
    assert lay["padded_bytes"] == lay["stripes"] * width >= size
    assert lay["padded_bytes"] - size < width
    assert lay["shard_bytes"] == lay["stripes"] * CHUNK
    value = bytes(range(256)) * (size // 256) + bytes(size % 256)
    got = reference_small.shards(value, K, M, CHUNK)
    assert got.shape == (K + M, lay["shard_bytes"])
    assert (got == reference.expected_shards(value, K, M, CHUNK)).all()
    # the data shards, read stripe by stripe, are the value and zeros
    back = got[:K].reshape(K, lay["stripes"], CHUNK).transpose(1, 0, 2)
    assert back.tobytes() == value + bytes(lay["padded_bytes"] - size)
    least = reference_small.least_bytes(size, K, M, CHUNK)
    assert least == {"at_rest": (K + M) * lay["shard_bytes"],
                     "link_up": lay["padded_bytes"],
                     "link_down": M * lay["shard_bytes"]}


def test_the_reference_holds_64_kib_to_a_number():
    assert reference_small.layout(SIZE, K, M, CHUNK) == {
        "stripes": 2, "padded_bytes": SIZE, "shard_bytes": 8192,
        "shards": 11}
    assert reference_small.least_bytes(SIZE, K, M, CHUNK) == {
        "at_rest": 90112, "link_up": 65536, "link_down": 24576}
    assert reference_small.store_bytes_per_user_byte(SIZE, K, M, CHUNK) \
        == reference_small.link_bytes_per_user_byte(SIZE, K, M, CHUNK) \
        == (K + M) / K == 1.375
    # one byte more is a third stripe: 1.375 is the floor, not the rule
    assert reference_small.store_bytes_per_user_byte(SIZE + 1, K, M, CHUNK) \
        == pytest.approx(11 * 3 * CHUNK / (SIZE + 1))


# -- the readers, on hand-made contexts ------------------------------------------------

OLD_STATS = {"jobs": 9, "batches": 7, "dec_jobs": 0, "dec_batches": 0,
             "crc_jobs": 0, "crc_batches": 0}
HOPS = {"sem_wait_us": 100.0, "pool_wait_us": 300.0, "stack_us": 50.0,
        "h2d_submit_us": 200.0, "launch_us": 150.0, "result_wait_us": 650.0,
        "finish_us": 80.0, "resume_us": 600.0, "scatter_us": 20.0}
PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def _batch(kind="enc", flush="linger", scale=1.0, **more):
    tags = {"kind": kind, "ops": 2, "bytes": 2 * SIZE, "device": "tpu:0",
            **{h: v * scale for h, v in HOPS.items()}, **more}
    if flush is not None:
        tags["flush"] = flush
    return {"tags": tags, "duration_us": 2150.0 * scale}


def _wait(us, kind="enc"):
    return {"tags": {"batch_ops": 2, **({"kind": kind} if kind else {})},
            "duration_us": us}


def _copy(h2d, d2h):
    return {s: {"referenced_bytes": 0, "copied_bytes": n,
                "copy_seconds": 0.0, "events": 1}
            for s, n in (("h2d", h2d), ("d2h", d2h))}


def _ctx(before=None, after=None, batches=(), waits=(), copy=None,
         kernel_s=None):
    cell = types.SimpleNamespace(config={
        "object_size": SIZE,
        "pool": {"k": K, "m": M, "stripe_unit": CHUNK}})
    copy = copy or (_copy(0, 0), _copy(0, 0))
    return types.SimpleNamespace(
        cell=cell, user_bytes={"write": 40 * SIZE, "read": 0},
        open={"offload": dict(before or {}), "copy": copy[0]},
        close={"offload": dict(after or {}), "copy": copy[1]},
        spans={"offload_batch": list(batches),
               "offload_queue_wait": list(waits)},
        trace=None if kernel_s is None else {"programs": {
            apply_bitmatrix_batched_roofline.PROGRAM: kernel_s}},
        peaks=None if kernel_s is None else PEAKS)


def _enc(jobs, batches, nbytes, **more):
    return dict(OLD_STATS, enc_jobs=jobs, enc_batches=batches,
                enc_bytes=nbytes, **more)


def _contexts_with_nothing_to_read():
    moved = (_copy(100, 10), _copy(100 + 10 * SIZE, 10 + 10 * SIZE * 3 // 8))
    return {
        # a run whose tracer and counters gave nothing at all
        "nothing": types.SimpleNamespace(
            cell=_ctx().cell, user_bytes={}, open={}, close={}, spans={},
            trace=None, peaks=None),
        # the parent's program: no `enc_*` counter, no `flush` tag, no
        # `kind` on a rider's wait, and a host batch has no hops
        "a_program_without_them": _ctx(
            OLD_STATS, dict(OLD_STATS, jobs=49, batches=27),
            batches=[{"tags": {"kind": "enc", "ops": 2, "device": "host"},
                      "duration_us": 9.0}],
            waits=[_wait(2500.0, kind=None)], copy=moved, kernel_s=0.01),
        # a window of decodes and device crc batches alone
        "other_kinds_batches": _ctx(
            _enc(5, 4, 4 * SIZE),
            _enc(5, 4, 4 * SIZE, dec_jobs=30, dec_batches=20,
                 crc_jobs=8, crc_batches=2),
            batches=[_batch("dec"), _batch("crc"), _batch("rep")],
            waits=[_wait(900.0, "dec"), _wait(700.0, "crc")],
            copy=moved, kernel_s=0.01),
        # counters only at the window's close (a service made inside it)
        "only_at_close": _ctx({}, _enc(40, 16, 40 * SIZE), copy=moved,
                              kernel_s=0.01),
    }


@pytest.mark.parametrize("name", list(NEW))
@pytest.mark.parametrize("case", ["nothing", "a_program_without_them",
                                  "other_kinds_batches", "only_at_close"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """None, never 0 and never an exception: the line leaves the metric
    out, as the driver expects of the parent commit."""
    assert _reader(name).read(_contexts_with_nothing_to_read()[case]) is None


@pytest.mark.parametrize("name", ["enc_link_bytes_per_byte",
                                  "enc_bitmatrix_roofline"])
def test_link_and_kernel_are_not_read_where_decodes_shared_them(name):
    """One link and one codec program serve both directions: beside a
    decode batch there are no bytes or device time that are the
    encodes' alone."""
    before = _enc(5, 4, 4 * SIZE)
    copy = (_copy(0, 0), _copy(40 * SIZE, 15 * SIZE))
    alone = _ctx(before, _enc(45, 20, 44 * SIZE), copy=copy, kernel_s=0.01)
    assert _reader(name).read(alone) is not None
    for other in ("dec", "crc"):
        shared = _ctx(before, _enc(45, 20, 44 * SIZE,
                                   **{other + "_batches": 1}),
                      copy=copy, kernel_s=0.01)
        assert _reader(name).read(shared) is None


def test_ops_per_batch_is_the_encodes_own_quotient():
    ctx = _ctx(_enc(10, 8, 10 * SIZE, dec_jobs=3, dec_batches=3),
               _enc(40, 20, 40 * SIZE, dec_jobs=30, dec_batches=30))
    assert _reader("enc_ops_per_batch").read(ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("rules,pct", [
    (["linger"] * 4, 100.0), (["full"] * 3, 0.0),
    (["linger", "linger", "linger", "full"], 75.0),
    (["linger", "asked"], 50.0)])
def test_linger_share_counts_the_encode_batches_flush_tags(rules, pct):
    batches = [_batch(flush=r) for r in rules] \
        + [_batch("dec", flush="full"), _batch(flush=None)]
    assert _reader("enc_linger_flush_pct").read(_ctx(batches=batches)) \
        == pytest.approx(pct)


def test_queue_wait_is_the_median_of_the_encodes_riders():
    waits = [_wait(us) for us in (2100.0, 2500.0, 9000.0)] \
        + [_wait(50000.0, "dec"), _wait(70000.0, kind=None)]
    assert _reader("enc_queue_wait_ms").read(_ctx(waits=waits)) \
        == pytest.approx(2.5)


def test_handoff_and_device_call_are_medians_over_the_encode_batches():
    batches = [_batch(scale=s) for s in (1.0, 2.0, 4.0)] \
        + [_batch("dec", scale=100.0), _batch("crc", scale=100.0)]
    ctx = _ctx(batches=batches)
    assert _reader("enc_handoff_ms").read(ctx) == pytest.approx(2.0)
    assert _reader("enc_device_call_ms").read(ctx) == pytest.approx(2.0)
    # the accepted readers of every kind's batches see the others too
    assert _reader("offload_handoff_ms").read(ctx) == pytest.approx(4.0)


def test_link_bytes_are_held_to_the_references_number():
    want = reference_small.link_bytes_per_user_byte(SIZE, K, M, CHUNK)
    ctx = _ctx(_enc(5, 4, 5 * SIZE), _enc(45, 20, 45 * SIZE),
               copy=(_copy(7, 7), _copy(7 + 40 * SIZE, 7 + 15 * SIZE)))
    assert _reader("enc_link_bytes_per_byte").read(ctx) == want == 1.375
    # rows padded on their way up would show: 48 rows staged for 40
    padded = _ctx(_enc(5, 4, 5 * SIZE), _enc(45, 20, 45 * SIZE),
                  copy=(_copy(7, 7), _copy(7 + 48 * SIZE, 7 + 18 * SIZE)))
    assert _reader("enc_link_bytes_per_byte").read(padded) \
        == pytest.approx(1.65)
    # an object of 65,537 bytes is three stripes: the user's bytes are
    # fewer than the encoded ones, and the quotient says so
    ctx.cell.config["object_size"] = SIZE + 1
    ctx.close["offload"]["enc_bytes"] = 5 * SIZE + 40 * 3 * K * CHUNK
    ctx.close["copy"] = _copy(7 + 40 * 3 * K * CHUNK, 7 + 40 * 3 * M * CHUNK)
    assert _reader("enc_link_bytes_per_byte").read(ctx) == pytest.approx(
        reference_small.link_bytes_per_user_byte(SIZE + 1, K, M, CHUNK))


def test_roofline_counts_unpadded_bytes_with_the_accepted_function():
    nbytes = 4000 * SIZE
    least = apply_bitmatrix_batched_roofline.least_seconds(
        nbytes, K, M, PEAKS)
    assert max(least, key=least.get) == "hbm"
    ctx = _ctx(_enc(0, 0, 0), _enc(4000, 1500, nbytes),
               kernel_s=least["hbm"] * 50)
    assert _reader("enc_bitmatrix_roofline").read(ctx) == pytest.approx(2.0)
    # no trace (a traced run on another backend): nothing
    ctx.trace = ctx.peaks = None
    assert _reader("enc_bitmatrix_roofline").read(ctx) is None
    # a trace in which the codec program never ran: nothing, not 0
    idle = _ctx(_enc(0, 0, 0), _enc(4000, 1500, nbytes), kernel_s=0.0)
    assert _reader("enc_bitmatrix_roofline").read(idle) is None


# -- the cell, served tiny on the CPU backend -------------------------------------------

@pytest.fixture(scope="module")
def own_shape(tmp_path_factory):
    """The cell as it is served on the chip but for its length and its
    clients: k=8 m=3 on eleven OSDs, 64 KiB objects, four in flight."""
    from ceph_tpu.offload import service

    cell = harness.load_cell(CELL, root=ROOT)
    cell.config = dict(cell.config,
                       pool=dict(cell.config["pool"], pg_num=8))
    cell.traffic = dict(cell.traffic, clients=4, warmup_ops=8,
                        payload_pool=8)
    kept = dict(service._DEFAULTS)
    try:
        return asyncio.run(harness.run_cell(
            cell, 2 ** 31 + 49, 0.9, True,
            str(tmp_path_factory.mktemp("own_shape")), time.monotonic(), ()))
    finally:
        service._DEFAULTS.update(kept)


def test_the_cell_at_the_pools_own_shape_is_correct(own_shape):
    line = own_shape["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert all(value == 0 == limit
               for _n, value, limit in own_shape["checks"])
    assert {n for n, _v, _l in own_shape["checks"]} >= {
        "shard_bytes_differing", "sample_read_mismatches", "fallback_ops",
        "encode_bytes_not_on_device", "osd_markdowns_under_load"}
    assert own_shape["info"]["compiles_in_window"] == 0
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_the_stores_and_the_link_hold_the_references_number(own_shape):
    metrics = own_shape["result"]["metrics"]
    want = reference_small.store_bytes_per_user_byte(SIZE, K, M, CHUNK)
    assert metrics["store_bytes_per_user_byte"]["value"] == want == 1.375
    # a batch that straddles an edge of the window is counted on one
    # side by the ledger and on the other by the service
    batches = own_shape["info"]["offload_batches"]
    assert metrics["enc_link_bytes_per_byte"]["value"] == pytest.approx(
        reference_small.link_bytes_per_user_byte(SIZE, K, M, CHUNK),
        rel=2.0 / batches)
    assert metrics["enc_link_bytes_per_byte"]["unit"] == "B/B"


def test_the_traced_line_carries_the_encodes_readers(own_shape):
    metrics = own_shape["result"]["metrics"]
    info = own_shape["info"]
    # six of the seven: the seventh reads the device trace, which a CPU
    # run has not
    assert set(NEW) - set(metrics) == {"enc_bitmatrix_roofline"}
    for name in set(NEW) & set(metrics):
        assert metrics[name]["unit"] == NEW[name][0]
        assert metrics[name]["value"] > 0
    # every batch of the cell is an encode's, so the service's two
    # counts of them agree (a job is counted when it is admitted, an
    # encode when its batch ends: the four in flight at an edge apart);
    # sixteen 64 KiB jobs never fill 8 MiB
    assert abs(metrics["enc_ops_per_batch"]["value"]
               * info["offload_batches"] - info["offload_jobs"]) <= 4
    assert 1.0 <= metrics["enc_ops_per_batch"]["value"] <= 4.0
    assert metrics["enc_linger_flush_pct"]["value"] == 100.0
    # the linger is 2 ms and a rider waits it out
    assert metrics["enc_queue_wait_ms"]["value"] >= 2.0
    assert not NOT_MINE & set(metrics)


def test_tiny_traced_run_has_neither_of_the_stores_shares(tmp_path):
    """What `test_store_direct.py`'s generated case meant for this
    cell (it is marked in tests/conftest.py: it wants exactly one of
    two accepted lists to name every cell): a correct tiny traced run,
    neither share on its line."""
    done, cell = _tiny(CELL, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert cell.config["object_size"] == SIZE    # `shrink` keeps the size
    assert not {"store_write_direct_pct", "store_read_direct_pct"} \
        & set(line["metrics"])
    assert line["metrics"]["enc_linger_flush_pct"]["value"] == 100.0
    assert all(value <= limit for _n, value, limit in done["checks"])


def test_tiny_untraced_run_reports_the_four_end_to_end_metrics(tmp_path):
    done, _cell = _tiny(CELL, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ops_s", "op_p50_ms", "op_p95_ms",
                                    "setup_s"}
    series = json.load(open(tmp_path / "series.json"))
    assert sum(series["completions"]) == done["info"]["samples"]


def test_a_rotten_shard_makes_the_run_incorrect(tmp_path):
    done, _cell = _tiny(CELL, control=("bitrot",), tmp=tmp_path)
    assert done["result"]["correct"] is False
    rows = {n: v for n, v, _l in done["checks"]}
    assert rows["shard_bytes_differing"] > 0
    assert rows["ops_failed"] == 0

