"""The three seams through which a configuration's files reach past
the pool and the OSDs (PR 41): `mon_config` (the mon, before the pool
is created), `objectstore` (the stores the OSDs boot on) and `events`
(a schedule on the window's clock: stop, start, out, in). Tiny, on the
CPU backend; what the harness keeps to itself is caught where the
window has closed and the cluster still stands."""
from __future__ import annotations

import asyncio
import os
import time
import types

import numpy as np
import pytest

from benchmarks import harness

SEED = 2 ** 31 + 41
#: the program's test setting, not upstream's 20 s: a stopped OSD is
#: marked down inside a tiny window
GRACE = {"osd_scrub_interval": 86400.0, "osd_heartbeat_grace": 1.5}


#: the cells accepted before any used `events` or another store
BEFORE_THE_SCHEDULE = ["rb4m_write", "rb4m_seqread", "rb4m_degraded_seqread",
                       "rb4m_scrub_seqread", "rb4m_fastread_seqread"]
#: the rows of every accepted cell's `checks`, in their order
ACCEPTED_ROWS = ["ops_failed", "read_mismatches", "events_failed",
                 "sample_read_mismatches", "shard_bytes_differing",
                 "fallback_ops", "breaker_trips", "device_failovers",
                 "osd_markdowns_under_load", "flight_events_lost",
                 "lanes_off_platform", "encode_bytes_not_on_device"]


def _cell(name, config=None, traffic=None):
    """A cell at `_tiny`'s sizes (k=2 m=1, 64 KiB objects, 4 clients)
    with a file's worth of keys laid over its configuration and its
    traffic, as a later PR's files would state them."""
    cell = harness.load_cell(name)
    cell.config = {**cell.config, "osds": 3, "object_size": 65536,
                   "pool": dict(cell.config["pool"], k=2, m=1, pg_num=8),
                   **(config or {})}
    cell.traffic = {**cell.traffic, "clients": 4, "warmup_ops": 8,
                    "payload_pool": 4, **(traffic or {})}
    if cell.traffic.get("preload_objects"):
        cell.traffic["preload_objects"] = 8
    return cell


def _run(cell, tmp, seconds=0.6, trace=False, seed=SEED):
    return asyncio.run(harness.run_cell(
        cell, seed, seconds, trace, str(tmp), time.monotonic()))


@pytest.fixture
def seen(monkeypatch):
    """The cluster as `final_checks` is handed it. `seen["settle"]`, if
    a test sets it, is awaited first: the wait a test needs that a run
    does not make (recovery's end, a map's arrival)."""
    got: dict = {}
    real = harness.final_checks

    async def final_checks(cell, gen, model, io, osds, pool, *rest):
        if "settle" in got:
            await got["settle"](osds, model)
        maps = [o.osdmap for o in osds if o.whoami not in rest[6]]
        maps.append(io.client.osdmap)
        got.update(
            osds=list(osds), stopped=list(rest[6]), model=model,
            harness_stopped=set(rest[-1]),
            pools=[{p.name: p for p in mp.pools.values()}[pool]
                   for mp in maps],
            states=[{i: (s.up, s.in_cluster) for i, s in mp.osds.items()}
                    for mp in maps],
            own=[o.config.get("osd_pool_default_ec_fast_read")
                 for o in osds],
            blobs={n: harness._shard_blobs(
                [o for o in osds if o.whoami not in rest[6]], pool, n)
                for n in model.names()})
        return await real(cell, gen, model, io, osds, pool, *rest)
    monkeypatch.setattr(harness, "final_checks", final_checks)
    return got


def _checks(done):
    return {n: v for n, v, _l in done["checks"]}


# -- mon_config --------------------------------------------------------------

@pytest.mark.parametrize("stated", [True, False, None])
def test_mon_config_is_in_force_when_the_pool_is_created(
        stated, tmp_path, seen):
    """The pool carries the mon's default from its creation, in every
    map, and no OSD's own option is touched."""
    config = {} if stated is None else {
        "mon_config": {"osd_pool_default_ec_fast_read": stated}}
    done = _run(_cell("rb4m_seqread", config), tmp_path)
    assert done["result"]["correct"] is True
    assert len(seen["pools"]) == 4
    assert [p.fast_read for p in seen["pools"]] == [bool(stated)] * 4
    assert seen["own"] == [False] * 3


@pytest.mark.parametrize("config,named", [
    ({"mon_config": {"osd_pool_default_ec_slow_read": True}},
     "osd_pool_default_ec_slow_read"),
    ({"mon_config": {"osd_heartbeat_grace": 20.0}}, "osd_heartbeat_grace"),
    ({"objectstore": "kstore"}, "kstore"),
])
def test_a_configuration_the_deployment_cannot_take_ends_the_run(
        config, named, tmp_path):
    """An option the mon does not declare (an OSD's among them) and a
    store there is none of: each exits with the name, before any op."""
    with pytest.raises(SystemExit) as e:
        _run(_cell("rb4m_write", config), tmp_path)
    assert "benchmark:" in str(e.value) and named in str(e.value)


# -- objectstore -------------------------------------------------------------

@pytest.mark.parametrize("kind,cls", [
    ("memstore", "MemStore"), ("filestore", "FileStore"),
    ("bluestore", "BlueStore")])
def test_each_store_gives_a_correct_run(kind, cls, tmp_path, seen):
    done = _run(_cell("rb4m_write", {"objectstore": kind}), tmp_path)
    assert done["result"]["correct"] is True
    assert done["result"]["failed"] == 0 < done["result"]["attempted"]
    checks = _checks(done)
    assert checks["shard_bytes_differing"] == 0
    assert checks["sample_read_mismatches"] == 0
    assert {type(o.store).__name__ for o in seen["osds"]} == {cls}
    # every object of the run has its k+m shards in those stores
    assert seen["blobs"] and all(
        sorted(b) == [0, 1, 2] for b in seen["blobs"].values())
    # a persistent store's directory does not outlive the run
    paths = {getattr(o.store, "path", None) for o in seen["osds"]} - {None}
    assert len(paths) == (0 if kind == "memstore" else 3)
    assert not any(os.path.exists(p) for p in paths)
    # its shards are read back from a fresh mount, the last row; a
    # memstore cell's rows are the accepted ones, name for name
    rows = [n for n, _v, _l in done["checks"]]
    assert rows[:-1 if kind != "memstore" else None] == ACCEPTED_ROWS
    if kind == "memstore":
        assert done["info"]["store_dir_bytes"] is None
    else:
        assert done["checks"][-1] == ("shard_bytes_lost_on_remount", 0, 0)
        # the run's objects, k+m shards each, are in those directories
        assert done["info"]["store_dir_bytes"] >= \
            len(seen["blobs"]) * 3 * 32768


@pytest.mark.parametrize("kind", ["filestore", "bluestore"])
def test_a_store_torn_before_the_remount_fails_the_run(kind, tmp_path):
    """The control kept as a test: between the close and the remount
    every file of one OSD's directory loses its second half (the block
    file and the KV's runs, or the journal and the blobs). The live
    comparisons all pass, since the process still holds what it wrote;
    the fresh mount does not, and `correct` is false."""
    cell = _cell("rb4m_write", {"objectstore": kind, "object_size": 262144})
    done = asyncio.run(harness.run_cell(
        cell, SEED, 0.6, False, str(tmp_path), time.monotonic(),
        ("torn_store",)))
    checks = _checks(done)
    assert checks.pop("shard_bytes_lost_on_remount") >= 131072
    assert set(checks.values()) == {0}
    assert done["result"]["correct"] is False
    assert done["result"]["failed"] == 0 < done["result"]["attempted"]


@pytest.mark.parametrize("kind,reads", [
    ("memstore", 1.5), ("bluestore", 0.0),
    pytest.param("filestore", 0.0, marks=pytest.mark.xfail(
        strict=True, raises=AttributeError,
        reason="FileStore inherits MemStore.used_bytes, which sums "
               "`obj.data`; a `_FileObject` has none (PERF.md, Open "
               "questions): a traced run on filestore ends there, for "
               "the PR that may touch ceph_tpu/objectstore/"))])
def test_a_traced_run_reads_each_stores_used_bytes(kind, reads, tmp_path):
    """`store_bytes_per_user_byte` is read in a traced run alone: k=2
    m=1 on MemStore, and 0.0 where the store does not count yet (the
    base class's answer)."""
    done = _run(_cell("rb4m_write", {"objectstore": kind}), tmp_path,
                trace=True)
    assert done["result"]["correct"] is True
    assert done["result"]["metrics"]["store_bytes_per_user_byte"][
        "value"] == pytest.approx(reads)


def test_memstore_has_nothing_to_tear(tmp_path):
    with pytest.raises(SystemExit) as e:
        asyncio.run(harness.run_cell(
            _cell("rb4m_write"), SEED, 0.6, False, str(tmp_path),
            time.monotonic(), ("torn_store",)))
    assert "torn_store" in str(e.value) and "memstore" in str(e.value)


def test_memstore_is_the_boots_own_default():
    """`memstore` hands `ephemeral_cluster` no factory: the accepted
    cells boot as they did, byte for byte."""
    made: list = []
    assert harness.store_factory("memstore", made) is None
    assert callable(harness.store_factory("filestore", made))
    assert made == []


# -- events ------------------------------------------------------------------

def _recovered(k_m=3):
    """A settle: wait until every object has all its shards on OSDs
    that run, the spare's among them."""
    async def settle(osds, model):
        up = [o for o in osds if o._stop_event is None]
        deadline = time.monotonic() + 30
        while not all(len(harness._shard_blobs(up, "bench", n)) == k_m
                      for n in model.names()):
            if time.monotonic() > deadline:
                raise TimeoutError("recovery never filled the spare")
            await asyncio.sleep(0.1)
    return settle


def test_an_osd_marked_out_by_an_event_recovers_onto_the_spare(
        tmp_path, seen):
    """k=2 m=1 on four OSDs: set-up stops one, the event marks the same
    one out 0.1 s into the window, and its shards are rebuilt on the
    spare, byte for byte the reference's. Reads, since a pool of k + 1
    takes no write with a shard down (`min_size` = k + 1)."""
    cell = _cell("rb4m_seqread", {"osds": 4, "osd_config": GRACE},
                 {"stop_osds": 1, "events": [
                     {"at_s": 0.1, "do": "osd_out", "osd": 0}]})
    seen["settle"] = _recovered()
    caught = types.SimpleNamespace(NAME="caught", UNIT="x", ctx=None)
    caught.read = lambda ctx: setattr(caught, "ctx", ctx)
    cell.readers.append(caught)
    done = _run(cell, tmp_path, seconds=1.0, trace=True)
    assert done["result"]["correct"] is True, done["checks"]
    victim = harness.draw_victims(SEED, 4, 1, [])[0]
    assert seen["stopped"] == [victim]
    (event,) = caught.ctx.events
    assert event == done["info"]["events"][0]
    assert (event["do"], event["osd"]) == ("osd_out", victim)
    assert 0.1 <= event["t_s"] < 1.0
    assert "caught" not in done["result"]["metrics"]
    assert [s["tags"] for s in caught.ctx.spans["bench_event"]] == [
        {"do": "osd_out", "osd": victim}]
    checks = _checks(done)
    assert checks["events_failed"] == 0
    assert checks["shard_bytes_differing"] == 0
    assert checks["osd_markdowns_under_load"] == 0
    # down and out in every map that is read; whole on the other three
    assert all(st[victim] == (False, False) for st in seen["states"])
    assert seen["blobs"] and all(
        sorted(b) == [0, 1, 2] for b in seen["blobs"].values())


def test_an_osd_stopped_and_started_inside_the_window_comes_back(
        tmp_path, seen):
    cell = _cell("rb4m_seqread", {"osd_config": GRACE},
                 {"events": [{"at_s": 0.1, "do": "stop_osd", "osd": 0},
                             {"at_s": 2.5, "do": "start_osd", "osd": 0}]})
    done = _run(cell, tmp_path, seconds=4.0)
    assert done["result"]["correct"] is True, done["checks"]
    victim = harness.draw_victims(SEED, 3, 0, cell.traffic["events"])[0]
    assert [(e["do"], e["osd"]) for e in done["info"]["events"]] == [
        ("stop_osd", victim), ("start_osd", victim)]
    assert done["info"]["events"][0]["t_s"] < done["info"]["events"][1]["t_s"]
    # running again when the window closed: its shards are compared,
    # its mark-down was the harness's own doing, and every map has it up
    assert seen["stopped"] == [] and seen["harness_stopped"] == {victim}
    assert len(seen["states"]) == 4
    assert all(st[victim] == (True, True) for st in seen["states"])
    assert seen["osds"][victim].config.get("osd_heartbeat_grace") == 1.5
    checks = _checks(done)
    assert checks["events_failed"] == 0
    assert checks["osd_markdowns_under_load"] == 0
    assert checks["shard_bytes_differing"] == 0
    assert all(sorted(b) == [0, 1, 2] for b in seen["blobs"].values())


def test_an_osd_marked_out_and_in_again_serves_as_before(tmp_path, seen):
    cell = _cell("rb4m_seqread", {"osds": 4},
                 {"events": [{"at_s": 0.1, "do": "osd_out", "osd": 1},
                             {"at_s": 0.5, "do": "osd_in", "osd": 1}]})

    async def settle(osds, model):
        await asyncio.sleep(0.5)
    seen["settle"] = settle
    done = _run(cell, tmp_path, seconds=1.2)
    assert done["result"]["correct"] is True, done["checks"]
    victims = harness.draw_victims(SEED, 4, 0, cell.traffic["events"])
    assert [(e["do"], e["osd"]) for e in done["info"]["events"]] == [
        ("osd_out", victims[1]), ("osd_in", victims[1])]
    assert seen["stopped"] == [] and seen["harness_stopped"] == set()
    assert all(st[victims[1]] == (True, True) for st in seen["states"])
    assert _checks(done)["events_failed"] == 0


@pytest.mark.parametrize("events,why", [
    ([{"at_s": 0.1, "do": "start_osd", "osd": 0}], "is running"),
    ([{"at_s": 5.0, "do": "osd_out", "osd": 0}], None),
])
def test_an_event_that_raises_or_never_runs_fails_the_run(
        events, why, tmp_path):
    """Starting an OSD that runs raises; an event due after the close
    never ran. Either is a row over its limit, and `correct` is false
    though every op was served."""
    done = _run(_cell("rb4m_seqread", None, {"events": events}), tmp_path)
    assert _checks(done)["events_failed"] == 1
    assert done["result"]["correct"] is False
    assert done["result"]["failed"] == 0 < done["result"]["attempted"]
    assert done["info"]["events"] == []
    if why:
        assert why in done["info"]["failures"][0]


@pytest.mark.parametrize("traffic,named", [
    ({"events": [{"at_s": 1, "do": "osd_destroy", "osd": 0}]},
     "osd_destroy"),
    ({"events": [{"at_s": -0.5, "do": "osd_out", "osd": 0}]}, "-0.5"),
    ({"events": [{"at_s": "2 s", "do": "osd_out", "osd": 0}]}, "'2 s'"),
    ({"events": [{"at_s": 1, "do": "osd_out", "osd": 0, "wait": True}]},
     "wait"),
    ({"events": [{"at_s": 1, "do": "osd_out"}]}, "at_s, do, osd"),
    ({"events": [{"at_s": 1, "do": "osd_out", "osd": "osd.3"}]}, "osd.3"),
    ({"events": [{"at_s": 1, "do": "osd_out", "osd": 3}]}, "4 OSDs"),
    ({"stop_osds": 4}, "4 OSDs"),
])
def test_a_schedule_the_harness_cannot_run_ends_the_run(
        traffic, named, tmp_path):
    with pytest.raises(SystemExit) as e:
        _run(_cell("rb4m_seqread", None, traffic), tmp_path)
    assert "benchmark:" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("seed", [0, 5, SEED, 2 ** 31 + 2 ** 20])
@pytest.mark.parametrize("stop", [0, 1, 3])
def test_the_victims_are_the_draw_stop_osds_has_always_made(seed, stop):
    """The first `stop` are what the accepted degraded cell stops for
    this seed; an event's index goes on through the other OSDs, each
    once, in an order that does not change with how far the file
    reaches."""
    old = sorted(int(x) for x in np.random.default_rng([seed, 4]).choice(
        11, size=stop, replace=False)) if stop else []
    assert harness.draw_victims(seed, 11, stop, []) == old
    far = [{"at_s": 0, "do": "osd_out", "osd": 10}]
    near = [{"at_s": 0, "do": "osd_out", "osd": stop}]
    all_of_them = harness.draw_victims(seed, 11, stop, far)
    assert all_of_them[:stop] == old
    assert sorted(all_of_them) == list(range(11))
    assert harness.draw_victims(seed, 11, stop, near) == \
        all_of_them[:stop + 1]


# -- the accepted cells run what they ran -------------------------------------

@pytest.mark.parametrize("name", BEFORE_THE_SCHEDULE)
def test_an_accepted_cell_uses_no_seam_but_the_fastread_cells_own(name):
    cell = harness.load_cell(name)
    assert harness.schedule_of(cell.traffic) == []
    assert "events" not in cell.traffic
    assert cell.config["objectstore"] == "memstore"
    assert harness.store_factory(cell.config["objectstore"], []) is None
    want = {"osd_pool_default_ec_fast_read": True} \
        if name == "rb4m_fastread_seqread" else None
    assert cell.config.get("mon_config") == want
    assert "osd_pool_default_ec_fast_read" not in cell.config["osd_config"]
    stop = cell.traffic.get("stop_osds", 0)
    assert len(harness.draw_victims(7, cell.config["osds"], stop, [])) == stop


def test_the_recovery_cell_uses_the_schedule_and_no_other_seam():
    """Two events, both mark-outs, the first of the OSD set-up stopped;
    MemStore, and nothing set on the mon."""
    cell = harness.load_cell("rb4m_recovery_write")
    events = harness.schedule_of(cell.traffic)
    assert [(e["do"], e["osd"]) for e in events] == [
        ("osd_out", 0), ("osd_out", 1)]
    assert cell.traffic["stop_osds"] == 1
    assert cell.config["objectstore"] == "memstore"
    assert harness.store_factory(cell.config["objectstore"], []) is None
    assert "mon_config" not in cell.config
    victims = harness.draw_victims(7, cell.config["osds"], 1, events)
    assert len(set(victims)) == 2
    assert victims[:1] == harness.draw_victims(7, cell.config["osds"], 1, [])


def test_the_entry_prints_each_check_beside_its_limit(
        tmp_path, monkeypatch, capsys):
    """`benchmarks.run` past its look for a chip: the numbers compared
    are the last lines of standard error and the last key of the
    result's line."""
    import json

    from benchmarks import run as entry

    monkeypatch.setattr(entry, "_pin_process", lambda argv: time.monotonic())
    monkeypatch.setattr(entry, "select_device", lambda chips: None)
    cell = _cell("rb4m_seqread", None, {"events": [
        {"at_s": 9.0, "do": "osd_in", "osd": 0}]})
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    assert entry.main(["--workload", "rb4m_seqread", "--seed", str(SEED),
                       "--seconds", "0.5", "--trace", "0",
                       "--out", str(tmp_path)]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and line["correct"] is False
    assert line["checks"]["events_failed"] == {"value": 1, "limit": 0}
    assert line["checks"]["ops_failed"] == {"value": 0, "limit": 0}
    last = err.strip().splitlines()[-len(line["checks"]):]
    assert last == [f"benchmark: check {n} = {c['value']} (limit "
                    f"{c['limit']})" for n, c in line["checks"].items()]
