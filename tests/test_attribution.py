"""Attribution-profiler tests (ISSUE 6): byte-exact copy-ledger
accounting over a known pipeline, the loop account (a synthetic loop's
time by label, collections, pauses, slices, nothing left installed,
hot-toggle via config), per-device offload utilization (fallback
batches attributed to `host`), the hand-offs of a staged dispatch as
tags on `offload_batch`, and the report→exporter contract
(`ceph_device`-labeled families, every report-merged logger renderable).
"""
from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu import offload
from ceph_tpu.ec import registry
from ceph_tpu.mgr.daemon import DaemonStateIndex
from ceph_tpu.mgr.exporter import render_metrics
from ceph_tpu.msg.frames import Frame, Tag
from ceph_tpu.utils import copytrack, loopprof, tracer
from ceph_tpu.utils.admin_socket import AdminSocket
from ceph_tpu.utils.buffer import BufferList
from ceph_tpu.utils.config import Config
from ceph_tpu.utils.perf_counters import PerfCountersCollection


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """The ledger is process-wide; each test reads its own deltas."""
    copytrack.reset()
    yield
    copytrack.reset()


# ---------------------------------------------------------------------------
# copy ledger: known pipeline -> exact bytes-copied
# ---------------------------------------------------------------------------

def test_ledger_frame_tx_rx_exact_bytes():
    segs = [b"a" * 512, b"b" * 256]
    blob = Frame(Tag.MESSAGE, segs).encode()
    snap = copytrack.snapshot()["stages"]
    # tx joins every segment into the wire blob exactly once (the old
    # assemble-then-bytes() path paid 2x)
    assert snap["frame_tx"]["copied_bytes"] == 768
    assert snap["frame_tx"]["events"] == 1
    # the scatter path (plain crc transport, frames at or over the
    # spill size) copies nothing under either codec: segments go to
    # the transport by reference and meter as referenced
    from ceph_tpu.msg import frames as frames_mod
    was_native = frames_mod.native_active()
    try:
        for n, use_native in enumerate([False] + [True] * was_native, 1):
            frames_mod.set_native(use_native)
            parts = Frame(Tag.MESSAGE, segs).encode_parts()
            assert parts[1] is segs[0] and parts[3] is segs[1]
            snap = copytrack.snapshot()["stages"]
            assert snap["frame_tx"]["copied_bytes"] == 768
            assert snap["frame_tx"]["referenced_bytes"] == n * 768
    finally:
        frames_mod.set_native(was_native)
    assert snap["frame_rx"]["copied_bytes"] == 0
    frame = Frame.decode(blob)
    snap = copytrack.snapshot()["stages"]
    # rx WINDOWS each segment out of the blob (zero-copy receive): the
    # payload meters as referenced, and nothing is copied
    assert snap["frame_rx"]["copied_bytes"] == 0
    assert snap["frame_rx"]["referenced_bytes"] == 768
    assert all(isinstance(s, memoryview) for s in frame.segments)
    assert frame.segments == segs


def test_ledger_bufferlist_copy_vs_reference():
    bl = BufferList()
    bl.append(b"x" * 100)                   # bytes -> owned copy
    snap = copytrack.snapshot()["stages"]["frame_to_buffer"]
    assert snap["copied_bytes"] == 100
    assert snap["referenced_bytes"] == 0
    bl.append(np.zeros(50, dtype=np.uint8))  # ndarray -> window, no copy
    snap = copytrack.snapshot()["stages"]["frame_to_buffer"]
    assert snap["copied_bytes"] == 100
    assert snap["referenced_bytes"] == 50
    bl.to_array()                            # 2 ptrs -> one concatenate
    staging = copytrack.snapshot()["stages"]["buffer_to_staging"]
    assert staging["copied_bytes"] == 150


@pytest.mark.parametrize("payload,kept", [
    ("bytes", True), ("readonly_view", True), ("bytearray", False),
    ("sliver_of_a_large_body", False)])
def test_ledger_store_stages_exact_bytes(payload, kept):
    """`store_write`: a payload the store keeps as it came is
    referenced, one that is copied on the way in is copied (a mutable
    buffer at `Transaction.write`'s snapshot, after which the bytes are
    kept: both; a sliver of a large body by the store). `store_read`:
    a window on a kept buffer is referenced, `bytes` of an object the
    store has written into is copied."""
    from ceph_tpu.objectstore import (CollectionId, Ghobject, MemStore,
                                      Transaction)
    assert copytrack.STAGES[-2:] == ("store_write", "store_read")
    cid, oid = CollectionId.make_pg(1, 0), Ghobject(pool=1, name="o")
    store = MemStore()
    store.queue_transaction(Transaction().create_collection(cid))
    raw = b"s" * 4096
    data = {"bytes": raw, "bytearray": bytearray(raw),
            "readonly_view": memoryview(bytearray(raw)).toreadonly(),
            "sliver_of_a_large_body":
                memoryview(bytes(64) + raw * 5).toreadonly()[64:64 + 4096],
            }[payload]
    store.queue_transaction(Transaction().write(cid, oid, 0, data))
    snap = copytrack.snapshot()["stages"]
    assert snap["store_write"]["copied_bytes"] == (0 if kept else 4096)
    assert snap["store_write"]["referenced_bytes"] == \
        (0 if payload.startswith("sliver") else 4096)
    assert store.read(cid, oid, 96, 1000) == raw[:1000]
    snap = copytrack.snapshot()["stages"]["store_read"]
    assert (snap["referenced_bytes"], snap["copied_bytes"]) == (1000, 0)
    # a write into the object makes it the store's own: reads copy
    store.queue_transaction(Transaction().write(cid, oid, 10, b"in"))
    assert store.read(cid, oid, 0, 12) == raw[:10] + b"in"
    snap = copytrack.snapshot()["stages"]
    assert (snap["store_read"]["referenced_bytes"],
            snap["store_read"]["copied_bytes"]) == (1000, 12)
    assert snap["store_write"]["copied_bytes"] == (0 if kept else 4096) + 2
    dump = copytrack.perf().dump()
    assert dump["copied_bytes_store_read"] == 12
    assert dump["referenced_bytes_store_read"] == 1000


def test_ledger_amplification_and_totals():
    copytrack.copied("h2d", 300, 0.001)
    copytrack.referenced("buffer_to_staging", 1000)
    copytrack.copied("d2h", 100)
    assert copytrack.amplification(100) == 4.0     # (300+100)/100
    assert copytrack.amplification(0) == 0.0
    snap = copytrack.snapshot()
    assert snap["copied_bytes_total"] == 400
    assert snap["referenced_bytes_total"] == 1000
    assert snap["copy_seconds_total"] == pytest.approx(0.001)


def test_ledger_perf_counter_mirror_syncs_on_dump():
    pc = copytrack.perf()
    assert PerfCountersCollection.instance().get("copyflow") is pc
    copytrack.copied("h2d", 128, 0.002)
    dump = pc.dump()
    assert dump["copied_bytes_h2d"] == 128
    assert dump["copy_micros_h2d"] == 2000
    # the mirror is pull-model: a later ledger reset zeroes it too
    copytrack.reset()
    assert pc.dump()["copied_bytes_h2d"] == 0


# ---------------------------------------------------------------------------
# the loop account
# ---------------------------------------------------------------------------

def _spin(seconds: float) -> None:
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pass


def _account(body, parts: dict | None = None
             ) -> tuple[dict, float, list[dict]]:
    """Run `body()` on a fresh loop under full tracing; returns the
    account's microseconds by label, the wall microseconds it was armed
    for, and the spans it closed; `parts` is filled with the
    microseconds by part."""
    parts = {} if parts is None else parts

    async def main():
        tracer.reset()
        tracer.enable(max_spans=65536)
        loopprof.reset()
        await asyncio.sleep(0)      # the arming turn ends unhooked
        t0 = time.perf_counter()
        await body()
        await asyncio.sleep(0)
        d = loopprof.dump()
        wall = (time.perf_counter() - t0) * 1e6
        tracer.disable()
        parts.update(d["parts_us"])
        return d["labels_us"], wall, tracer.collector().spans()
    try:
        return asyncio.run(main())
    finally:
        tracer.disable()
        tracer.reset()


def test_account_charges_a_synthetic_loop_to_its_labels():
    """Self time by label: `ms_dispatch` stops being charged the moment
    `osd_op` opens inside it, a bare callback falls to its code's
    package, and the labels sum, with `idle`, to the wall clock. Each
    stretch is held against what the body itself measured of it: under
    six workers a 50 ms spin or sleep takes what the machine gives it,
    and the account charges wall time."""
    took: dict[str, float] = {}

    def timed(label, fn, *args):
        t0 = time.perf_counter()
        fn(*args)
        took[label] = (time.perf_counter() - t0) * 1e6

    async def body():
        loop = asyncio.get_running_loop()
        with tracer.span("ms_dispatch"):
            timed("msgr", _spin, 0.050)
            with tracer.span("osd_op"):
                timed("osd", _spin, 0.030)
                t0 = time.perf_counter()
                await asyncio.sleep(0.02)       # parked: idle
                took["idle"] = (time.perf_counter() - t0) * 1e6
        done = loop.create_future()

        def bare():
            timed("unattributed", time.sleep, 0.040)
            done.set_result(None)
        loop.call_soon(bare)
        await done

    labels, wall, _spans = _account(body)
    nominal = {"msgr": 50_000, "osd": 30_000, "unattributed": 40_000,
               "idle": 20_000}
    for label, least in nominal.items():
        assert took[label] >= least * 0.99, label
        # the stretch itself, and the little the loop does around it
        assert labels[label] == pytest.approx(
            took[label], rel=0.05, abs=3_000), label
    assert sum(labels.values()) == pytest.approx(wall, rel=0.01)
    assert set(labels) == set(loopprof.LABELS) | {"idle"}


def test_account_takes_a_collection_out_of_the_label_it_interrupted():
    import gc

    async def body():
        with tracer.span("osd_op"):
            t0 = time.perf_counter()
            gc.collect()
            took.append((time.perf_counter() - t0) * 1e6)

    junk = [[i] for i in range(300_000)]    # something to walk
    took: list[float] = []
    labels, _wall, _spans = _account(body)
    assert junk and took[0] > 2_000
    assert labels["gc"] == pytest.approx(took[0], rel=0.2)
    assert labels["osd"] < 0.2 * took[0]


def test_account_records_one_pause_with_its_four_facts():
    """`time.sleep(0.7)` on the loop: exactly one `loop_pause`, whose
    stack names the sleeping line, with CPU ~ 0 (blocked, not working),
    no collection inside it and a punctual watchdog."""
    from ceph_tpu.utils import flight

    async def body():
        await asyncio.sleep(0.25)   # a fresh sample of the CPU clock
        loop = asyncio.get_running_loop()
        done = loop.create_future()

        def sleeper():
            time.sleep(0.7)
            done.set_result(None)
        loop.call_soon(sleeper)
        await done

    cursor = flight.last_seq()
    _labels, _wall, spans = _account(body)
    pauses = [s for s in spans if s["name"] == "loop_pause"]
    assert len(pauses) == 1
    facts = pauses[0]["tags"]
    assert facts["duration_s"] == pytest.approx(0.7, abs=0.05)
    assert pauses[0]["duration_us"] == pytest.approx(700_000, rel=0.1)
    assert "sleeper" in facts["callback"]
    assert "test_attribution.py" in facts["stack"][0]
    assert "in sleeper" in facts["stack"][0]
    assert facts["cpu_s"] < 0.15
    assert facts["gc_s"] < 0.05
    assert facts["watchdog_late_s"] < 0.2
    events = [e for e in flight.events_since(cursor)["events"]
              if e["type"] == "loop_pause"]
    assert len(events) == 1
    assert events[0]["detail"]["duration_s"] == facts["duration_s"]


def test_account_closes_slices_that_add_up_to_their_length():
    async def body():
        for _ in range(30):
            _spin(0.005)
            await asyncio.sleep(0.01)

    _labels, _wall, spans = _account(body)
    slices = [s for s in spans if s["name"] == "loop_slice"]
    assert len(slices) >= 3
    for s in slices:
        tags = s["tags"]
        by = sum(tags[k + "_us"] for k in loopprof.LABELS + ("idle",))
        assert by == pytest.approx(s["duration_us"], rel=0.01)
        assert tags["callbacks"] > 0
        assert len(tags["lag_hist"]) == len(tags["lag_edges_ms"]) + 1
        assert set(tags) == {k + "_us" for k in loopprof.LABELS + ("idle",)} \
            | {"callbacks", "lag_hist", "lag_edges_ms", "parts", "instr"}
        assert s["parent_id"] is None
    assert sum(sum(s["tags"]["lag_hist"]) for s in slices) > 10


def _spin_in(part: str, seconds: float) -> None:
    with tracer.section(part):
        _spin(seconds)


def test_section_books_its_stretch_to_its_part_and_the_rest_to_the_tasks(
        monkeypatch):
    """A task whose code the table maps to `msgr.rx_frame` (by file and
    name) spins 30 ms of its own and 20 ms inside a section: the section
    is `msgr.codec`'s, across an await too, and the label is the sum."""
    monkeypatch.setattr(loopprof, "LABEL_OF_PATH", (
        ("/test_attribution.py", "reader", "msgr.rx_frame"),
        ("/test_attribution.py", "writer", "msgr.tx_frame"))
        + loopprof.LABEL_OF_PATH)

    async def reader():
        _spin(0.030)
        with tracer.section("msgr.codec"):
            _spin(0.010)
            await asyncio.sleep(0.02)           # parked: idle
            _spin(0.010)
        _spin_in("msgr.rx_alloc", 0.010)

    async def writer():
        _spin(0.015)

    async def body():
        await asyncio.gather(asyncio.create_task(reader()),
                             asyncio.create_task(writer()))

    parts: dict = {}
    labels, wall, _spans = _account(body, parts)
    assert parts["msgr.rx_frame"] == pytest.approx(30_000, abs=5_000)
    assert parts["msgr.codec"] == pytest.approx(20_000, abs=5_000)
    assert parts["msgr.rx_alloc"] == pytest.approx(10_000, abs=5_000)
    assert parts["msgr.tx_frame"] == pytest.approx(15_000, abs=5_000)
    assert labels["msgr"] == pytest.approx(
        sum(v for k, v in parts.items() if k.startswith("msgr.")), abs=1)
    assert labels["msgr"] == pytest.approx(75_000, abs=8_000)
    assert sum(labels.values()) == pytest.approx(wall, rel=0.01)
    assert set(parts) == {k for k in loopprof.KEYS if "." in k}


def test_a_slices_parts_sum_to_their_labels_and_the_labels_to_its_length():
    async def body():
        for _ in range(12):
            with tracer.span("osd_op"):
                _spin(0.004)
                with tracer.span("ec_read"):
                    _spin(0.003)
            _spin_in("msgr.codec", 0.003)
            with tracer.span("ms_dispatch", "osd.3"):
                _spin(0.002)
            await asyncio.sleep(0.005)

    _labels, _wall, spans = _account(body)
    slices = [s for s in spans if s["name"] == "loop_slice"]
    assert len(slices) >= 3
    seen = dict.fromkeys(loopprof.PARTS, 0.0)
    for s in slices:
        tags = s["tags"]
        assert sum(tags[k + "_us"] for k in loopprof.LABELS + ("idle",)) \
            == pytest.approx(s["duration_us"], rel=0.01)
        assert set(tags["parts"]) == {f"{lab}.{p}" for lab, ps in
                                      loopprof.PARTS.items() for p in ps}
        for label in loopprof.PARTS:
            mine = sum(v for k, v in tags["parts"].items()
                       if k.startswith(label + "."))
            assert mine == pytest.approx(tags[label + "_us"], abs=0.01)
            seen[label] += mine
    assert seen["msgr"] > 20_000 and seen["osd"] > 60_000
    total = {k: sum(s["tags"]["parts"][k] for s in slices)
             for k in slices[0]["tags"]["parts"]}
    assert total["osd.pg"] > total["osd.ec"] > total["osd.subop"] > 10_000
    assert total["osd.other"] == total["msgr.other"] == 0


def _instr_of(slices: list[dict]) -> dict:
    """The slices' `instr` tags summed: `by_kind`, `in_part`, counts."""
    out = {"by_kind": {}, "in_part": {}, "spans": 0, "sections": 0}
    for s in slices:
        tag = s["tags"]["instr"]
        for key in ("by_kind", "in_part"):
            for k, v in tag[key].items():
                out[key][k] = out[key].get(k, 0.0) + v
        out["spans"] += tag["spans"]
        out["sections"] += tag["sections"]
    return out


@pytest.mark.parametrize("n_spans,n_sections", [(0, 0), (48, 0), (0, 80),
                                                (160, 320), (33, 17)])
def test_a_slice_counts_the_spans_and_sections_that_closed_on_its_loop(
        n_spans, n_sections):
    """N spans (mapped, unmapped, and one in three opened and finished
    outside a CM) and M sections on the loop: the slices' `instr` counts
    exactly them, whatever share of them was timed."""
    async def body():
        for i in range(max(n_spans, n_sections)):
            if i < n_spans:
                if i % 3 == 2:
                    tracer.start_span("ms_send", "osd.1").finish()
                else:
                    with tracer.span(("osd_op", "bench_event")[i % 3]):
                        pass
            if i < n_sections:
                with tracer.section("msgr.codec"):
                    pass
            if i % 16 == 0:
                await asyncio.sleep(0.005)
        await asyncio.sleep(0.12)       # the last slice closes

    _labels, _wall, spans = _account(body)
    got = _instr_of([s for s in spans if s["name"] == "loop_slice"])
    assert (got["spans"], got["sections"]) == (n_spans, n_sections)
    assert len([s for s in spans if s["name"] != "loop_slice"]) == n_spans


def test_a_slices_instruments_are_booked_by_kind_and_by_part_alike():
    """The observer's own time, as an "of which": by kind and by part it
    is the same sum, it is within the slice's busy time, every kind is
    named, the parts are the account's, and the labels still add up to
    the slice's length with nothing moved out of them."""
    async def body():
        for _ in range(40):
            for _ in range(8):
                with tracer.span("osd_op"):
                    with tracer.section("msgr.codec"):
                        _spin(0.0002)
                with tracer.span("ms_dispatch", "osd.3"):
                    pass
            await asyncio.sleep(0.004)
        await asyncio.sleep(0.12)

    _labels, _wall, spans = _account(body)
    slices = [s for s in spans if s["name"] == "loop_slice"]
    assert len(slices) >= 3
    for s in slices:
        tags = s["tags"]
        instr = tags["instr"]
        assert set(instr) == {"by_kind", "in_part", "spans", "sections"}
        assert set(instr["by_kind"]) == set(loopprof.INSTR_KINDS)
        assert set(instr["in_part"]) <= set(loopprof.KEYS) - {"idle"}
        assert not any(k.endswith("_us") for k in instr)
        assert all(v >= 0 for v in instr["by_kind"].values())
        whole = sum(instr["by_kind"].values())
        assert whole == pytest.approx(sum(instr["in_part"].values()),
                                      rel=1e-6, abs=1e-3)
        assert whole <= s["duration_us"] - tags["idle_us"]
        assert sum(tags[k + "_us"] for k in loopprof.LABELS + ("idle",)) \
            == pytest.approx(s["duration_us"], rel=0.01)
    got = _instr_of(slices)
    assert got["spans"] == 640 and got["sections"] == 320
    # calibrated, not timed: a unit a callback (what a slice held over
    # for the next, were it too full, the last one may still hold)
    assert got["by_kind"]["hook"] == pytest.approx(
        sum(s["tags"]["callbacks"] for s in slices)
        * loopprof.hook_unit_ns() / 1e3, rel=0.05)
    # 960 of them, one in sixteen timed: each kind was met and costs a
    # span more than a section, the hook a callback about what it was
    # calibrated at, the ticker its few lines
    assert got["by_kind"]["span"] > got["by_kind"]["section"] > 0
    assert 0 < got["by_kind"]["span"] / 640 < 200       # us a span
    assert 100 < loopprof.hook_unit_ns() < 50_000
    assert got["by_kind"]["roll"] > 0
    # a span's and a section's way in is charged to the part outside it
    assert got["in_part"].get("osd.pg", 0) > 0          # codec inside osd_op
    assert "background" in got["in_part"]               # the ticker's own


def test_a_span_leaves_no_instr_while_the_account_is_disarmed():
    """Head sampling without the account: spans are made and kept, no
    slice is closed, nothing of the self-account is counted, and the
    tracer holds no hook of it."""
    tracer.reset()
    tracer.set_sampling(rate=1.0)
    try:
        async def main():
            for _ in range(64):
                with tracer.span("osd_op"):
                    with tracer.section("msgr.codec"):
                        pass
                tracer.start_span("ms_send").finish()
            await asyncio.sleep(0.06)
            assert loopprof.installed_loops() == []
        before = tracer._timed_k
        asyncio.run(main())
        assert tracer._timed_k == before
        assert tracer._acct_span is tracer._acct_section is None
        assert tracer._acct_open is tracer._acct_close is None
        assert tracer._acct_closed is None
        spans = tracer.collector().spans()
        assert len(spans) == 128
        assert not any(s["name"] == "loop_slice" or "instr" in s["tags"]
                       for s in spans)
    finally:
        tracer.set_sampling(rate=0.0)
        tracer.reset()
    assert loopprof.hook_unit_ns() >= 0.0


def test_section_with_no_loop_running_is_a_no_op():
    """Armed by the tracer, outside any loop: nothing to charge, nothing
    installed, the books as they were."""
    tracer.enable()
    try:
        before = loopprof.dump()["parts_us"]
        with tracer.section("msgr.codec"):
            _spin(0.002)
        assert loopprof.installed_loops() == []
        assert loopprof.dump()["parts_us"] == before
    finally:
        tracer.disable()
        tracer.reset()


def test_disarmed_account_leaves_nothing_installed():
    """With the tracer disabled `Handle._run` is asyncio's own,
    `gc.callbacks` is as found, no loopprof thread lives, and
    `tracer.span()` and `tracer.section()` are the shared no-op."""
    import gc
    import threading
    run_before = asyncio.events.Handle._run
    gc_before = list(gc.callbacks)

    async def main():
        loop = asyncio.get_running_loop()
        tracer.enable()
        assert loop in loopprof.installed_loops()
        assert asyncio.events.Handle._run is not run_before
        assert len(gc.callbacks) == len(gc_before) + 1
        assert any(t.name == "loopprof-watchdog"
                   for t in threading.enumerate())
        select = loop._selector.select
        with tracer.span("osd_op"):
            await asyncio.sleep(0.02)
        tracer.disable()
        assert loop._selector.select is not select
        assert loopprof.installed_loops() == []

    try:
        asyncio.run(main())
    finally:
        tracer.disable()
        tracer.reset()
    assert asyncio.events.Handle._run is run_before
    assert gc.callbacks == gc_before
    assert not any(t.name.startswith("loopprof")
                   for t in threading.enumerate())
    assert tracer.span("osd_op") is tracer.span("pg_op")    # the no-op
    assert tracer.section("msgr.codec") is tracer.span("osd_op")


def test_tracer_enable_without_a_loop_arms_at_the_first_span():
    """`tracer.enable()` with no running loop installs nothing; the
    first mapped span entered on a loop arms that loop, and a loop
    left armed is pruned when it closes."""
    import threading
    tracer.enable()
    try:
        assert loopprof.installed_loops() == []
        assert asyncio.events.Handle._run is loopprof._ORIG_RUN

        async def main():
            with tracer.span("bench_open"):     # unmapped: moves no label
                pass
            assert loopprof.installed_loops() == []
            with tracer.span("rados_op"):
                assert loopprof.installed_loops() == \
                    [asyncio.get_running_loop()]
        asyncio.run(main())                     # left armed, loop closed
        assert loopprof.installed_loops() == []
    finally:
        tracer.disable()
        tracer.reset()
    assert asyncio.events.Handle._run is loopprof._ORIG_RUN
    assert not any(t.name.startswith("loopprof")
                   for t in threading.enumerate())


def test_account_hot_toggle_via_config_and_reset():
    cfg = Config()
    loopprof.register_config(cfg)
    assert cfg.get("profiler_enabled") is False

    async def body():
        loop = asyncio.get_running_loop()
        loopprof.maybe_install(cfg)          # disabled: tracks, no arm
        assert loop not in loopprof.installed_loops()
        cfg.set("profiler_enabled", True)    # observer arms live
        assert loop in loopprof.installed_loops()
        tracer.enable()                      # a second owner...
        tracer.disable()                     # ...leaves the operator's
        assert loop in loopprof.installed_loops()
        await asyncio.sleep(0.02)
        cfg.set("profiler_enabled", False)   # ... and disarms live
        assert loop not in loopprof.installed_loops()

    asyncio.run(body())
    assert loopprof.dump()["wall_us"] > 0
    cleared = loopprof.reset()
    assert cleared["cleared_wall_us"] > 0
    assert loopprof.dump()["wall_us"] == 0


def test_profile_dump_admin_socket_command(tmp_path):
    asok = AdminSocket(str(tmp_path / "t.asok"))
    out = asok.execute({"prefix": "profile dump"})["result"]
    assert set(out) >= {"enabled", "loop_busy_fraction", "labels_us",
                        "parts_us", "wall_us", "shards", "lag_hist",
                        "hook_unit_ns"}
    assert asok.execute({"prefix": "profile reset"})[
        "result"]["cleared_wall_us"] >= 0


# ---------------------------------------------------------------------------
# per-device offload utilization
# ---------------------------------------------------------------------------

def _impl(k=4, m=2):
    return registry.factory("tpu", {"k": str(k), "m": str(m)})


def test_device_batches_and_fallback_attribution():
    async def body():
        impl = _impl()
        svc = offload.get_service()
        stripes = np.zeros((2, 4, 1024), dtype=np.uint8)
        await svc.encode(impl, stripes)
        # healthy dispatch lands on the jax device label (cpu:N here)
        dev_keys = [k for k in svc.device_stats if k != "host"]
        assert len(dev_keys) == 1
        d = svc.device_stats[dev_keys[0]]
        assert d["batches"] >= 1 and d["ops"] >= 1
        assert d["bytes"] >= stripes.nbytes
        assert d["busy_s"] > 0.0
        assert d["fallback_ops"] == 0
        # now break the device path: the fallback batch must be
        # attributed to the fixed "host" label
        impl.encode_stripes = lambda batch: (_ for _ in ()).throw(
            RuntimeError("device gone"))
        await svc.encode(impl, stripes)
        host = svc.device_stats["host"]
        assert host["fallback_ops"] >= 1
        assert host["batches"] >= 1
        assert host["busy_s"] > 0.0
        # the report-path view mirrors the same attribution
        dm = svc.device_metrics()
        assert dm["host"]["offload_device_fallback_ops"] >= 1
        assert dm[dev_keys[0]]["offload_device_ops"] >= 1
        assert svc.status()["devices"][dev_keys[0]]["ops"] >= 1

    asyncio.run(body())


def test_offload_batch_hops_sum_to_the_span():
    """The eight hops `_device_call` and `_run_batch` stamp on the batch
    span from where the work happens, without serializing anything,
    account for its duration, the batch's two host copies in the
    staging pool (`stack_us`, `finish_us`) among them; the semaphore
    wait before it is a tag as well."""
    from ceph_tpu.offload.service import _HOPS
    from ceph_tpu.osd import ec_util

    async def body():
        impl = _impl()
        svc = offload.get_service()
        stripes = np.zeros((64, 4, 4096), dtype=np.uint8)
        await svc.encode(impl, stripes)             # compile outside
        tracer.reset()
        tracer.enable(max_spans=4096)
        for _ in range(5):
            await svc.encode(impl, stripes)
        sinfo = ec_util.StripeInfo(4, 4 * 4096)
        await ec_util.encode_async(sinfo, impl, bytes(4 * 4 * 4096),
                                   service=svc)
        tracer.disable()
        return tracer.collector().spans()

    try:
        spans = asyncio.run(body())
    finally:
        tracer.disable()
        tracer.reset()
    batches = [s for s in spans if s["name"] == "offload_batch"]
    assert len(batches) == 6
    for s in batches:
        tags = s["tags"]
        inside = sum(tags[h] for h in _HOPS + ("scatter_us",))
        assert inside == pytest.approx(s["duration_us"], rel=0.02)
        assert all(tags[h] >= 0 for h in _HOPS + ("scatter_us",))
        assert tags["sem_wait_us"] >= 0
        assert {"stack_us", "finish_us"} < set(_HOPS)
        assert tags["device"].startswith("cpu:")
    enc = [s for s in spans if s["name"] == "ec_encode"]
    assert len(enc) == 1 and enc[0]["tags"]["assemble_us"] >= 0
    assert enc[0]["tags"]["assemble_us"] < enc[0]["duration_us"]
    # four stripes of 4 + 2 chunks: the rider's six planes are strided
    # and copied by its finisher, inside the batch's finish_us
    assert enc[0]["tags"]["copy_bytes"] == 6 * 4 * 4096
    assert 0 < enc[0]["tags"]["copy_us"] <= enc[0]["tags"]["assemble_us"] \
        <= batches[-1]["tags"]["finish_us"]


# ---------------------------------------------------------------------------
# report -> exporter family contract
# ---------------------------------------------------------------------------

def test_device_metrics_render_with_ceph_device_label():
    index = DaemonStateIndex()
    index.report({
        "daemon_name": "osd.0", "service": "osd",
        "schema": {"copyflow_copied_bytes_h2d": {"type": "counter"}},
        "counters": {"copyflow_copied_bytes_h2d": 4096},
        "device_metrics": {
            "tpu:0": {"offload_device_bytes": 123,
                      "offload_device_busy_seconds": 0.5},
            "host": {"offload_device_bytes": 7}},
    })
    text = render_metrics(None, index=index)
    assert ('ceph_offload_device_bytes{ceph_daemon="osd.0",'
            'ceph_device="tpu:0"} 123') in text
    assert ('ceph_offload_device_bytes{ceph_daemon="osd.0",'
            'ceph_device="host"} 7') in text
    assert 'ceph_device="tpu:0"} 0.5' in text
    # the ledger counter merged from the report renders as a family too
    assert "# TYPE ceph_copyflow_copied_bytes_h2d counter" in text
    # exactly one TYPE line per family
    assert text.count("# TYPE ceph_offload_device_bytes ") == 1


def test_every_report_merged_logger_is_exportable():
    """The runtime half of radoslint's report-export-consistency rule:
    every extra_loggers name the OSD merges into its MgrClient reports
    must resolve in the process-wide collection once armed, so its
    counters reach the exporter family list."""
    from ceph_tpu.utils import sanitizer
    copytrack.perf()
    loopprof.perf()
    sanitizer.perf()

    async def body():
        offload.get_service()       # registers the "offload" logger

    asyncio.run(body())
    coll = PerfCountersCollection.instance()
    for name in ("offload", "sanitizer", "loopprof", "copyflow"):
        pc = coll.get(name)
        assert pc is not None, f"extra_logger {name!r} unregistered"
        assert pc.dump(), f"logger {name!r} exports no counters"


# ---------------------------------------------------------------------------
# the parts of `msgr` and `osd` on a live cluster
# ---------------------------------------------------------------------------

PARTS_ON_A_CLUSTER = [f"{label}.{p}" for label in ("msgr", "osd")
                      for p in loopprof.PARTS[label]
                      # a healthy pool neither scrubs nor recovers here:
                      # tests/test_backfill_reservation.py charges that
                      if p not in ("scrub", "recovery")]


@pytest.fixture(scope="module")
def cluster_parts() -> dict:
    """Microseconds by part of one loop that boots an EC pool on three
    OSDs and serves a few writes and reads of 256 KiB (chunks over the
    spill's 64 KiB, so bodies are allocated) with the account armed."""
    from ceph_tpu.tools.cluster_boot import ephemeral_cluster

    async def main():
        async with ephemeral_cluster(3, prefix="parts-") \
                as (client, _osds, _mon):
            tracer.reset()
            tracer.enable(max_spans=65536)
            loopprof.reset()
            try:
                await client.command({
                    "prefix": "osd erasure-code-profile set", "name": "prof",
                    "profile": {"plugin": "jerasure", "k": "2", "m": "1",
                                "technique": "reed_sol_van"}})
                await client.pool_create("parts", pg_num=4,
                                         pool_type="erasure",
                                         erasure_code_profile="prof")
                io = client.ioctx("parts")
                value = bytes(range(256)) * 1024
                for i in range(6):
                    await io.write_full(f"obj{i}", value)
                for i in range(6):
                    assert await io.read(f"obj{i}") == value
                await asyncio.sleep(1.1)        # a round of the OSDs' pings
                return loopprof.dump()["parts_us"]
            finally:
                tracer.disable()
                tracer.reset()
    return asyncio.run(main())


@pytest.mark.parametrize("part", PARTS_ON_A_CLUSTER)
def test_every_part_is_charged_on_a_live_cluster(cluster_parts, part):
    """Each row of the table of parts finds its work: by code (the
    loops, the selector's callbacks, the queue), by span (PG, EC, the
    OSD's `ms_dispatch`) or by section; and what is left over in `other`
    is the lesser share of its label."""
    assert cluster_parts[part] > 0
    label, _, name = part.partition(".")
    if name == "other":
        whole = sum(v for k, v in cluster_parts.items()
                    if k.startswith(label + "."))
        assert cluster_parts[part] < whole / 3
