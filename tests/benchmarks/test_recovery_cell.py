"""The recovery cell (`rb4m_recovery_write`): its entries and files, its
nine readers on hand-made spans, the plain reference for recovery, and
the cell's own files run tiny on the CPU backend at the shape
`test_benchmarks.shrink` learns from them: an OSD to spare for each
mark-out, a parity more for the OSD set-up stops."""
from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

from benchmarks import harness, reference, reference_recovery
from benchmarks.layer_metrics import loop_share, recovery_spans
from tests.benchmarks.test_benchmarks import BENCH, ROOT, run_tiny, shrink

CELL = "rb4m_recovery_write"
CONFIG = "radosbench_ec83_tpu_recovery"
NEW = {"recovery_active_pct": ("%", "osd/pg+osd/ec_backend", "ops_s"),
       "recovery_objects_s": ("objects/s", "osd/pg+osd/ec_backend", "ops_s"),
       "ec_recover_ms": ("ms", "osd/pg+osd/ec_backend", "op_p50_ms"),
       "backfill_reserve_wait_ms": ("ms", "osd/reserver", "op_p95_ms"),
       "backfill_pgs_done": ("pgs", "osd/reserver", "ops_s"),
       "backfill_target_peak": ("count", "osd/reserver", "op_p95_ms"),
       "recovery_objects_twice": ("objects", "osd/pg+osd/ec_backend",
                                  "ops_s"),
       "client_rate_in_recovery_pct": ("%", "osd/pg+osd/ec_backend",
                                       "ops_s"),
       "osd_recovery_ms_per_op": ("ms/op", "osd/pg+osd/ec_backend", "ops_s")}
#: accepted entries that list this cell since PR 44: the write and the
#: decode readers that read right on a window which both encodes and
#: decodes (benchmarks/README.md says which stay out, and why)
JOINED = ["ec_encode_ms", "store_commit_ms", "loop_offload_pct",
          "decode_ops_per_batch", "decode_handoff_ms",
          "decode_device_call_ms", "store_write_direct_pct"]


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- the entries and the files ---------------------------------------------------

def entries_stand(bench, root=ROOT):
    """The cell's entries, each found by its name: one configuration,
    one workload, nine readers of its own in their order, and the
    accepted entries that list the cell, written out."""
    config = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": CELL,
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["source"]) <= 200
    assert config["source"] != {c["name"]: c for c in bench["configs"]}[
        "radosbench_ec83_tpu_degraded"]["source"]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(NEW)))
    assert names[at:at + 9] == list(NEW)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, layer, moves) in NEW.items():
        assert by[name] == {
            "name": name, "unit": unit, "better": by[name]["better"],
            "source": "program_span", "layer": layer, "moves": moves,
            "workloads": by[name]["workloads"]}
        assert CELL in by[name]["workloads"]
    assert [n for n in names if n not in NEW
            and CELL in by[n].get("workloads", ())] == JOINED
    # what every cell reports, and none of what mixes the two kinds of
    # batch or finds nothing to read here
    cell = harness.load_cell(CELL, root=root)
    loaded = {r.NAME for r in cell.readers}
    assert set(NEW) | set(JOINED) <= loaded
    assert not {"offload_ops_per_batch", "offload_handoff_ms",
                "offload_device_call_ms", "link_bytes_per_byte",
                "ec_decode_ms", "apply_bitmatrix_batched_roofline",
                "decode_bitmatrix_roofline"} & loaded
    assert {"loop_busy_pct", "device_idle_pct", "compiles_in_window",
            "osd_pg_ms_per_op", "msgr_frames_per_op"} <= loaded


def test_the_cell_is_one_config_one_workload_and_nine_readers_by_name():
    entries_stand(BENCH)
    for name, (unit, layer, moves) in NEW.items():
        mod = _reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, layer, moves)


@pytest.mark.parametrize("name", JOINED)
def test_an_accepted_entry_lists_this_cell(name):
    """The write cell's and the degraded cell's readers list their
    cells; this one joined them in PR 44, after the cells they named."""
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL in by[name]["workloads"]
    assert {"rb4m_write", "rb4m_degraded_seqread"} & set(
        by[name]["workloads"])
    assert name in {r.NAME for r in harness.load_cell(CELL).readers}


def test_the_files_are_the_siblings_with_two_more_osds_and_two_events():
    cell = harness.load_cell(CELL)
    sibling = harness.load_cell("rb4m_degraded_seqread").config
    cfg = cell.config
    assert cfg["osds"] == sibling["osds"] + 2 == 13
    assert cfg["pool"] == sibling["pool"] and cfg["min_size"] == 9
    assert cfg["objectstore"] == "memstore" and "mon_config" not in cfg
    assert cfg["osd_config"] == dict(
        sibling["osd_config"], osd_max_backfills=1,
        osd_recovery_max_active=3)
    assert sorted(cfg["reduced"]) == sorted(sibling["reduced"])
    assert {"out_leaves_acting_at_once", "no_preemption"} <= \
        set(cfg["departures"])
    assert cell.traffic == {
        "op": "write", "clients": 16, "preload_objects": 128,
        "warmup_ops": 64, "payload_pool": 64, "stop_osds": 1,
        "events": [{"at_s": 2, "do": "osd_out", "osd": 0},
                   {"at_s": 16, "do": "osd_out", "osd": 1}]}
    events = harness.schedule_of(cell.traffic)
    victims = harness.draw_victims(2 ** 31 + 9, cfg["osds"], 1, events)
    assert len(set(victims)) == 2
    assert victims[:1] == harness.draw_victims(2 ** 31 + 9, 13, 1, [])
    # the program declares both options: the parent exits on the name
    from ceph_tpu.osd.daemon import OSD
    osd = OSD(0, [("127.0.0.1", 1)])
    for key, value in cfg["osd_config"].items():
        osd.config.set(key, value)


# -- the readers on hand-made spans ------------------------------------------------

def _span(name, start, dur_s, service="osd.1", **tags):
    return {"name": name, "service": service, "start": float(start),
            "duration_us": dur_s * 1e6, "tags": tags}


def _recover(end_s, oid="o1", target=4, interval=9, dur_s=0.08):
    return _span("ec_recover", 100.0 + end_s - dur_s, dur_s, oid=oid,
                 pgid="1.2", target=target, interval=interval, need=[3],
                 helpers=list(range(8)), chunks=128)


def _reserve(end_s, pgid, target, state="granted", service="osd.1",
             local_us=1000.0, remote_us=3000.0, rejects=0):
    return _span("backfill_reserve", 100.0 + end_s - 0.004, 0.004, service,
                 pgid=pgid, target=[target], kind="backfill",
                 local_us=local_us, remote_us=remote_us, rejects=rejects,
                 state=state)


def _done(at_s, pgid, target, state="done", service="osd.1", objects=5):
    return _span("backfill_done", 100.0 + at_s, 0.0, service, pgid=pgid,
                 target=[target], objects=objects, bytes=objects * 524288,
                 held_us=1e6, state=state)


def _ctx(window_s=10.0, ops=200, **spans):
    by = {"bench_open": [_span("bench_open", 100.0, 0.0, "")]}
    by.update(spans)
    return types.SimpleNamespace(spans=by, window_s=window_s, ops=ops)


def _read(name, ctx):
    return _reader(name).read(ctx)


def test_readers_find_nothing_on_a_program_without_the_spans():
    """The parent's `ec_recover` carries no `oid`, and it opens neither
    `backfill_reserve` nor `backfill_done`: None, never 0."""
    bare = _span("ec_recover", 101.0, 0.05, need=[3], helpers=[0], chunks=1)
    flat = {"name": "loop_slice", "duration_us": 50000.0,
            "tags": {k + "_us": 1.0 for k in loop_share.LABELS}}
    for ctx in (_ctx(), _ctx(ec_recover=[bare], loop_slice=[flat]),
                types.SimpleNamespace(spans={}, window_s=10.0, ops=5)):
        for name in NEW:
            assert _read(name, ctx) is None, name


def test_seconds_of_recovery_and_the_rates_in_and_out_of_them():
    """Shards end in seconds 2, 2, 3 and 7 of a 10 s window (and one in
    its broken last second, left out); the clients complete 2 a second
    beside them and 5 a second without."""
    recs = [_recover(2.2), _recover(2.9, "o2"), _recover(3.5, "o3"),
            _recover(7.1, "o4"), _recover(10.4, "o5")]
    ops = [_span("rados_op", 100.0 + s + 0.1 * i, 0.05, "client")
           for s in range(10) for i in range(2 if s in (2, 3, 7) else 5)]
    ctx = _ctx(window_s=10.6, ec_recover=recs, rados_op=ops)
    assert recovery_spans.active_seconds(ctx) == {2, 3, 7}
    assert _read("recovery_active_pct", ctx) == pytest.approx(30.0)
    assert _read("recovery_objects_s", ctx) == pytest.approx(4 / 3)
    assert _read("ec_recover_ms", ctx) == pytest.approx(80.0)
    assert _read("client_rate_in_recovery_pct", ctx) == pytest.approx(40.0)
    assert _read("recovery_objects_twice", ctx) == 0.0
    # fewer than two seconds of either kind: no rate to compare
    one = _ctx(ec_recover=[_recover(2.2)], rados_op=ops)
    assert _read("client_rate_in_recovery_pct", one) is None
    assert _read("recovery_active_pct", one) == pytest.approx(10.0)
    # a window with no whole second has no share
    assert _read("recovery_active_pct",
                 _ctx(window_s=0.6, ec_recover=[_recover(0.3)])) is None


def test_an_object_rebuilt_twice_in_one_interval_is_told_from_the_next():
    recs = [_recover(1.0, "o1", 4, 9), _recover(2.0, "o1", 4, 9),
            _recover(3.0, "o1", 4, 11), _recover(4.0, "o1", 5, 9)]
    assert _read("recovery_objects_twice", _ctx(ec_recover=recs)) == 1.0


def test_reservations_waits_ends_and_a_grant_never_released():
    """Three PGs of two primaries: 1.1 holds osd.4 from 1.0 to 3.0 s,
    1.2 asks osd.4 and is granted only at 3.2 s after two refusals and
    never lets go inside the window, 1.3 loses its grant to an interval
    change; 1.5 gave up without a grant."""
    res = [_reserve(1.0, "1.1", 4), _reserve(3.2, "1.2", 4, rejects=2,
                                             remote_us=2_100_000.0),
           _reserve(1.5, "1.3", 6, service="osd.2", local_us=0.0,
                    remote_us=500.0),
           _reserve(2.0, "1.5", 6, state="interval_change",
                    service="osd.2")]
    ends = [_done(3.0, "1.1", 4), _done(2.5, "1.3", 6, "interval_change",
                                        "osd.2")]
    ctx = _ctx(backfill_reserve=res, backfill_done=ends)
    assert _read("backfill_reserve_wait_ms", ctx) == pytest.approx(4.0)
    assert _read("backfill_pgs_done", ctx) == 1.0
    assert _read("backfill_target_peak", ctx) == 1.0
    holds = recovery_spans.holds(ctx)
    assert [(h[3], h[1] is None) for h in holds] == \
        [("1.1", False), ("1.3", False), ("1.2", True)]
    events = recovery_spans.reservation_events(ctx, t_close=110.0)
    assert reference_recovery.check_reservations(events, 1) == []
    # the same target granted to a second PG while the first holds it
    ctx.spans["backfill_reserve"].append(_reserve(2.0, "1.7", 4,
                                                  service="osd.3"))
    assert _read("backfill_target_peak", ctx) == 2.0
    broken = reference_recovery.check_reservations(
        recovery_spans.reservation_events(ctx, t_close=110.0), 1)
    # twice: beside 1.1 till 3.0 s, then beside 1.2 from 3.2 s on
    assert [(osd, role, n) for _t, osd, role, n in broken] == \
        [(4, "remote", 2)] * 2
    # markers and no `done` among them is a reading: 0, not None
    none_done = _ctx(backfill_done=[_done(1.0, "1.1", 4, "aborted")])
    assert _read("backfill_pgs_done", none_done) == 0.0
    assert _read("backfill_target_peak", none_done) is None


def test_the_loops_part_is_read_through_the_shared_helper():
    tags = {k + "_us": 0.0 for k in loop_share.LABELS}
    tags["parts"] = {"osd.recovery": 3000.0, "osd.ec": 500.0}
    ctx = _ctx(ops=6, loop_slice=[{"name": "loop_slice",
                                   "duration_us": 50000.0, "tags": tags}] * 2)
    assert _read("osd_recovery_ms_per_op", ctx) == pytest.approx(1.0)


# -- the plain reference ---------------------------------------------------------------

def test_reference_rebuilds_a_shard_both_ways_and_catches_a_flipped_byte():
    k, m, chunk = 4, 2, 4096
    value = np.random.default_rng(42).bytes(3 * k * chunk - 100)
    shards = reference.expected_shards(value, k, m, chunk)
    for position in range(k + m):
        survivors = [j for j in range(k + m) if j != position]
        got = reference_recovery.rebuilt_shard(value, k, m, chunk, position,
                                               survivors)
        assert np.array_equal(got, shards[position])
        # at r = 2: another position is gone too
        assert np.array_equal(reference_recovery.rebuilt_shard(
            value, k, m, chunk, position, survivors[1:]), shards[position])
        blob = bytearray(shards[position].tobytes())
        assert reference_recovery.shard_differs(
            bytes(blob), value, k, m, chunk, position, survivors) == 0
        blob[7] ^= 0x20
        assert reference_recovery.shard_differs(
            bytes(blob), value, k, m, chunk, position, survivors) == 1
        assert reference_recovery.shard_differs(
            bytes(blob[:-1]), value, k, m, chunk, position, survivors) \
            == len(blob)
    with pytest.raises(ValueError):
        reference_recovery.rebuilt_shard(value, k, m, chunk, 0, [1, 2, 3])
    # the two ways share the field and nothing else: they meet the program
    from ceph_tpu.ec import gf256
    row = reference_recovery.apply_row(shards[:k], k, m, k + 1)
    assert np.array_equal(row, gf256.mat_vec_apply(
        gf256.reed_sol_van_matrix(k, m), shards[:k])[1])


def test_reference_plans_what_each_interval_has_to_rebuild():
    """A 2+1 PG on OSDs 0, 1, 2 with 3 and 4 to spare: osd.2 dies (a
    hole), is marked out (3 takes position 2), then 0 is marked out and
    3 moves to its place while 4 takes position 2."""
    plan = reference_recovery.rebuild_plan([
        {"acting": {0: [0, 1, 2]}, "written": {0: ["a", "b"]}},
        {"acting": {0: [0, 1, None]}, "written": {0: ["c"]}},
        {"acting": {0: [0, 1, 3]}, "written": {0: ["d"]}},
        {"acting": {0: [3, 1, 4]}}])
    assert plan[0] == plan[1] == set()
    assert plan[2] == {("a", 2, 3), ("b", 2, 3), ("c", 2, 3)}
    # the mover holds position 2's chunks, not position 0's
    assert plan[3] == {(o, 0, 3) for o in "abcd"} | \
        {(o, 2, 4) for o in "abcd"}


def test_reservation_checker_counts_each_role_on_each_osd():
    ok = [(1.0, 3, "local", 1), (1.0, 5, "remote", 1),
          (2.0, 3, "local", -1), (2.0, 5, "remote", -1),
          (2.0, 4, "local", 1), (2.0, 5, "remote", 1),     # same instant
          (1.5, 5, "local", 1)]       # a target is a primary besides
    assert reference_recovery.check_reservations(ok, 1) == []
    two = ok + [(1.2, 9, "local", 1), (1.3, 5, "remote", 1),
                (1.8, 9, "local", -1), (1.8, 5, "remote", -1)]
    assert reference_recovery.check_reservations(two, 1) == \
        [(1.3, 5, "remote", 2)]
    assert reference_recovery.check_reservations(two, 2) == []
    assert reference_recovery.check_reservations(
        [(1.0, 3, "local", -1)], 1) == [(1.0, 3, "local", -1)]
    with pytest.raises(ValueError):
        reference_recovery.check_reservations([(1.0, 3, "scrub", 1)], 1)


# -- the cell's own files, tiny ---------------------------------------------------------

def _tiny_recovery(trace, tmp, seconds=5.0, control=(), m=2):
    """The cell's files as `shrink` cuts them (six OSDs and 2+2: four
    positions, two OSDs to spare, `min_size` 3 of the 3 that one
    stopped OSD leaves; the events as far into the window as
    the cell's are; a device batch bounded at four objects), with more
    objects to rebuild and a heartbeat grace a run on the CPU can wait
    out. `m` 3 on seven OSDs leaves an object readable with two
    positions not yet rebuilt and a third rotted, as 8+3 does."""
    cell = shrink(harness.load_cell(CELL), seconds)
    assert (cell.config["osds"], cell.config["pool"]["m"]) == (6, 2)
    assert [e["at_s"] for e in cell.traffic["events"]] == [
        pytest.approx(2 * seconds / 40), pytest.approx(16 * seconds / 40)]
    cell.config = dict(
        cell.config, osds=4 + m,
        pool=dict(cell.config["pool"], m=m),
        osd_config=dict(cell.config["osd_config"], osd_heartbeat_grace=6.0))
    cell.traffic = dict(cell.traffic, preload_objects=24)
    seen: dict = {}
    real = harness.Ctx

    def ctx(**kw):
        seen["ctx"] = real(**kw)
        return seen["ctx"]
    harness.Ctx = ctx
    try:
        done = run_tiny(cell, trace, control, seconds, tmp)
    finally:
        harness.Ctx = real
    return done, cell, seen.get("ctx")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _tiny_recovery(True, tmp_path_factory.mktemp("recovery"))


def test_tiny_traced_run_is_correct_with_both_events_inside(traced):
    done, _cell, _ctx_seen = traced
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    checks = {n: (v, limit) for n, v, limit in done["checks"]}
    assert all(v == 0 and limit == 0 for v, limit in checks.values())
    assert {"events_failed", "osd_markdowns_under_load",
            "shard_bytes_differing", "fallback_ops"} <= set(checks)
    assert done["info"]["compiles_in_window"] == 0
    events = done["info"]["events"]
    assert [e["do"] for e in events] == ["osd_out", "osd_out"]
    assert events[0]["t_s"] < events[1]["t_s"] < 5.0
    assert events[0]["osd"] != events[1]["osd"]


def test_tiny_traced_run_reports_the_nine_and_the_accepted_families(traced):
    done, cell, _ctx_seen = traced
    m = {k: v["value"] for k, v in done["result"]["metrics"].items()}
    units = {k: v["unit"] for k, v in done["result"]["metrics"].items()}
    # a run this short need not have two quiet seconds to compare with
    assert set(NEW) - {"client_rate_in_recovery_pct"} <= set(m)
    assert all(units[n] == NEW[n][0] for n in NEW if n in m)
    assert m["backfill_target_peak"] == 1.0
    assert m["recovery_objects_twice"] == 0.0
    assert m["recovery_active_pct"] >= 40.0
    assert m["recovery_objects_s"] > 0 and m["ec_recover_ms"] > 0
    assert m["backfill_pgs_done"] >= 8      # a PG once an interval
    assert m["backfill_reserve_wait_ms"] >= 0
    assert m["osd_recovery_ms_per_op"] > 0
    # the accepted write and decode readers that list the cell since
    # PR 44, and none of those that would mix the two kinds of batch
    assert set(JOINED) <= set(m)
    assert m["ec_encode_ms"] > 0 and m["store_commit_ms"] > 0
    assert 0.0 < m["store_write_direct_pct"] <= 100.0
    assert m["decode_ops_per_batch"] >= 1.0
    assert m["decode_handoff_ms"] >= 0 and m["decode_device_call_ms"] > 0
    assert not {"offload_ops_per_batch", "offload_handoff_ms",
                "offload_device_call_ms", "link_bytes_per_byte",
                "ec_decode_ms"} & set(m)
    # what every cell reports, here too
    assert 50.0 < m["msgr_acks_carried_pct"] <= 100.0
    assert 1.0 <= m["msgr_sends_per_op"] <= \
        m["msgr_frames_per_op"] + m["msgr_ctrl_frames_per_op"]
    assert 0.0 <= m["msgr_rx_direct_pct"] <= 100.0
    assert m["msgr_recvs_per_mib"] > 0.0
    assert 0.0 <= m["msgr_tx_direct_pct"] <= 100.0
    assert m["compiles_in_window"] == 0
    declared = {r.NAME for r in cell.readers}
    assert set(m) <= declared


def test_tiny_traced_runs_parts_add_up_with_recoverys_among_them(traced):
    done, _cell, ctx = traced
    m = {k: v["value"] for k, v in done["result"]["metrics"].items()}
    by = loop_share.totals(ctx)
    osd = sum(m[f"osd_{p}_ms_per_op"] for p in
              ("pg", "ec", "subop", "queue", "other", "recovery"))
    from benchmarks.layer_metrics import loop_parts
    osd += loop_parts.ms_per_op(ctx, "osd.scrub")       # 0: no reader here
    assert osd == pytest.approx(by["osd"] / ctx.ops / 1000.0, rel=0.01)
    msgr = sum(m[f"msgr_{p}_ms_per_op"] for p in
               ("rx_sock", "rx_alloc", "rx_frame", "codec", "tx_frame",
                "tx_sock", "dispatch", "handler", "other"))
    assert msgr == pytest.approx(by["msgr"] / ctx.ops / 1000.0, rel=0.01)
    assert m["loop_cpu_ms_per_op"] == pytest.approx(
        (sum(by.values()) - by["idle"]) / ctx.ops / 1000.0)


def test_tiny_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    done, _cell, _ctx_seen = _tiny_recovery(False, tmp_path, seconds=3.0)
    line = json.loads(json.dumps(done["result"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"ops_s", "op_p50_ms", "op_p95_ms",
                                    "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert all(value <= limit for _n, value, limit in done["checks"])
    series = json.load(open(os.path.join(tmp_path, "series.json")))
    assert sum(series["completions"]) == done["info"]["samples"]


def test_a_rotten_rebuilt_or_written_shard_fails_the_run(tmp_path):
    done, _cell, _ctx_seen = _tiny_recovery(
        False, tmp_path, seconds=3.0, control=("bitrot", "flip_read"), m=3)
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["shard_bytes_differing"] == 1
    assert checks["sample_read_mismatches"] == 1
    assert done["result"]["correct"] is False
