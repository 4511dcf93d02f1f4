"""The readers of the stores' stages of the copy ledger
(`store_write_direct_pct`, `store_read_direct_pct`), on hand-built
snapshots and in a tiny traced run of each cell."""
from __future__ import annotations

import asyncio
import time
import types

import pytest

from tests.benchmarks.test_benchmarks import BENCH, CELLS, _tiny
from tests.benchmarks.test_msgr_rx import ROOT
from benchmarks import harness

STAGE = {"store_write_direct_pct": "store_write",
         "store_read_direct_pct": "store_read"}
#: the cells each entry lists, read from the entry itself: a cell that
#: a later PR enters is looked up where that PR wrote it, or nowhere
WORKLOADS = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
             if m["name"] in STAGE}
MOVES = {"store_write_direct_pct": "op_p50_ms",
         "store_read_direct_pct": "ops_s"}
NEW = list(STAGE)
MIB = 2 ** 20


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _stage(referenced, copied):
    return {"referenced_bytes": referenced, "copied_bytes": copied,
            "copy_seconds": 0.0, "events": 3}


def _ctx(before, after):
    return types.SimpleNamespace(open={"copy": before},
                                 close={"copy": after})


def entries_stand(bench):
    """PR 34 appended them after the scrub cell's entries; PR 41 took
    four entries out before them, so the place is found by name. What
    each lists begins with PR 34's four cells, the write cell apart; a
    later cell that writes or reads may join (PR 44: the recovery
    cell's writes and pushes)."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 2] == NEW
    assert names[at - 2:at] == ["scrub_errors_found",
                                "scrub_pgs_without_round"]
    lists = {m["name"]: m["workloads"] for m in bench["per_layer"]
             if m["name"] in STAGE}
    assert lists["store_write_direct_pct"][0] == "rb4m_write"
    assert lists["store_read_direct_pct"][:3] == [
        "rb4m_seqread", "rb4m_degraded_seqread", "rb4m_scrub_seqread"]
    assert not set(lists["store_write_direct_pct"]) & set(
        lists["store_read_direct_pct"])
    for entry in bench["per_layer"][at:at + 2]:
        name = entry["name"]
        assert entry == {"name": name, "unit": "%", "better": "higher",
                         "source": "program_counter", "layer": "objectstore",
                         "moves": MOVES[name], "workloads": lists[name]}
        mod = _reader(name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
            name, "%", "objectstore", MOVES[name])


def test_the_two_entries_stand_after_the_scrub_cells_and_list_their_cells():
    entries_stand(BENCH)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["no_stage", "only_at_close",
                                  "no_bytes_in_the_window", "no_copy_group"])
def test_reader_finds_nothing_where_there_is_nothing_to_read(name, case):
    """The parent commit's ledger has no such stage: the reader returns
    nothing there and does not raise; a window in which no such byte
    moved has no share."""
    stage = STAGE[name]
    old = {"frame_tx": _stage(9, 9), "h2d": _stage(0, 5)}
    ctx = {
        "no_stage": _ctx(old, old),
        "only_at_close": _ctx(old, dict(old, **{stage: _stage(MIB, 1)})),
        "no_bytes_in_the_window": _ctx({stage: _stage(MIB, 10)},
                                       {stage: _stage(MIB, 10)}),
        "no_copy_group": types.SimpleNamespace(open={}, close={}),
    }[case]
    assert _reader(name).read(ctx) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("direct,copied,pct", [
    (10 * MIB, MIB, 100.0 * 10 / 11),
    (11 * MIB, MIB, 100.0 * 11 / 12),
    (0, 2 * MIB, 0.0),
    (8 * MIB, 0, 100.0),
    (9998, 2, 99.98),
])
def test_value_is_a_share_of_the_windows_deltas(name, direct, copied, pct):
    stage, other = STAGE[name], STAGE[[n for n in NEW if n != name][0]]
    before = {stage: _stage(7 * MIB, 3 * MIB), other: _stage(1, 1)}
    after = {stage: _stage(7 * MIB + direct, 3 * MIB + copied),
             other: _stage(5 * MIB, 1)}
    assert _reader(name).read(_ctx(before, after)) == pytest.approx(pct)


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run_reports_the_stores_share(cell, tmp_path):
    """A write cell commits shards inside its window and reports how
    many of their bytes the stores kept; a read cell serves shards and
    reports how many left as windows: all of them, since nothing in a
    cell writes into a stored shard. A cell that neither entry lists
    (the one that reads fast: its reads are the read cell's) has
    neither on its line."""
    done, _cell = _tiny(cell, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    mine = [n for n in NEW if cell in WORKLOADS[n]]
    assert len(mine) == (0 if cell == "rb4m_fastread_seqread" else 1)
    assert not (set(NEW) - set(mine)) & set(line["metrics"])
    if not mine:
        return
    got = line["metrics"][mine[0]]
    assert got["unit"] == "%"
    if "read" in cell:
        assert got["value"] == 100.0
    else:
        # k=2 m=1: two peers' shards kept as they came, the primary's
        # own snapshotted once and then kept (3 of 3 + 1), less the
        # 32 KiB shards that came many to an envelope and were copied
        # out of it by the quarter rule
        assert 30.0 <= got["value"] < 100.0


def test_write_cell_at_the_pools_own_shape_keeps_ten_shards_of_eleven(
        tmp_path):
    """The cell as it is served on the chip but for its length: k=8 m=3
    on eleven OSDs, 4 MiB objects, so 512 KiB shards that ride their own
    frames or two to an envelope and are all kept. Ten arrive as
    read-only windows on rx bodies; the primary's own is snapshotted
    once by `Transaction.write` (copied) and then kept (referenced):
    11 of 12."""
    cell = harness.load_cell("rb4m_write", root=ROOT)
    cell.config = dict(cell.config,
                       pool=dict(cell.config["pool"], pg_num=8))
    cell.traffic = dict(cell.traffic, clients=4, warmup_ops=4,
                        payload_pool=4)
    done = asyncio.run(harness.run_cell(
        cell, 2 ** 31 + 34, 1.5, True, str(tmp_path), time.monotonic(), ()))
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert 90.0 <= line["metrics"]["store_write_direct_pct"]["value"] <= 92.0
    assert line["metrics"]["store_bytes_per_user_byte"]["value"] == \
        pytest.approx(1.375)
    assert "store_read_direct_pct" not in line["metrics"]
