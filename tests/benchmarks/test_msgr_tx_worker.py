"""The two readers of the send worker's counters
(`ceph_tpu/msg/rxworker.py`; `tx_worker_bytes`, `tx_worker_cpu_ns` in
the `msgr` perf logger), on hand-built snapshots and in tiny traced
runs: frames under the worker's line read 0.0, frames over it do not."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from tests.benchmarks.test_benchmarks import (BENCH, _tiny, run_tiny,  # noqa: E402
                                              shrink)

NEW = ["msgr_tx_worker_pct", "msgr_tx_worker_busy_pct"]
MIB = 2 ** 20


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


def _ctx(before, after, window_s=10.0):
    return types.SimpleNamespace(open={"msgr": before},
                                 close={"msgr": after}, window_s=window_s)


def _counters(direct, copied, worker, cpu_ns, **more):
    return dict(tx_direct_bytes=direct, tx_copied_bytes=copied, tx_sends=3,
                tx_worker_bytes=worker, tx_worker_cpu_ns=cpu_ns, **more)


def entries_stand(bench):
    """Found by name, so that a later PR's entries do not fail it."""
    names = [m["name"] for m in bench["per_layer"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    assert names.index(NEW[1]) == names.index(NEW[0]) + 1
    # after every entry that stood before them
    assert names.index(NEW[0]) > names.index("kv_maintenance_ms_per_op")
    assert names.index(NEW[0]) > names.index("msgr_rx_worker_busy_pct")
    for n in NEW:
        assert "workloads" not in by[n]     # every cell sends
        assert (by[n]["source"], by[n]["layer"], by[n]["moves"],
                by[n]["unit"]) == ("program_counter", "msg/messenger",
                                   "ops_s", "%")
    assert [by[n]["better"] for n in NEW] == ["higher", "lower"]


def test_the_two_entries_are_appended_and_their_readers_agree():
    entries_stand(BENCH)
    for n in NEW:
        mod = _reader(n)
        entry = {m["name"]: m for m in BENCH["per_layer"]}[n]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (n, entry["unit"], entry["layer"], entry["moves"])


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["parent", "counter_only_at_close",
                                  "no_msgr_group", "nothing_sent",
                                  "empty_window"])
def test_reader_finds_nothing_on_a_parent_without_the_counters(name, case):
    """The parent commit has no send worker and no such counters: the
    line leaves the metric out, and nothing raises."""
    parent = dict(tx_direct_bytes=MIB, tx_copied_bytes=10, tx_sends=3)
    more = {k: v + MIB for k, v in parent.items()}
    ctx = {
        "parent": _ctx(parent, more),
        "counter_only_at_close": _ctx(parent,
                                      _counters(2 * MIB, 20, MIB, 5)),
        "no_msgr_group": types.SimpleNamespace(open={}, close={},
                                               window_s=10.0),
        "nothing_sent": _ctx(_counters(MIB, 10, MIB, 5),
                             _counters(MIB, 10, MIB, 5)),
        "empty_window": _ctx(_counters(MIB, 10, MIB, 5),
                             _counters(MIB, 10, MIB, 5), window_s=0.0),
    }[case]
    if (name, case) == (NEW[1], "nothing_sent"):
        assert _reader(name).read(ctx) == 0.0   # an idle thread is 0% busy
    else:
        assert _reader(name).read(ctx) is None


@pytest.mark.parametrize("direct,copied,worker,cpu_ms,window_s,pct,busy", [
    (99 * MIB, MIB, 95 * MIB, 4000, 10.0, 95.0, 40.0),
    (8 * MIB, 8 * MIB, 0, 0, 40.0, 0.0, 0.0),       # all under the line
    (4 * MIB, 0, 4 * MIB, 500, 0.5, 100.0, 100.0),
    (3 * MIB, MIB, MIB, 1, 1.0, 25.0, 0.1),
    (0, 4 * MIB, 0, 0, 2.0, 0.0, 0.0),              # an onwire session's
])
def test_values_are_deltas_over_the_window(direct, copied, worker, cpu_ms,
                                           window_s, pct, busy):
    before = _counters(7 * MIB, 3 * MIB, 5 * MIB, 10 ** 9)
    after = _counters(7 * MIB + direct, 3 * MIB + copied, 5 * MIB + worker,
                      10 ** 9 + cpu_ms * 10 ** 6)
    ctx = _ctx(before, after, window_s)
    assert _reader(NEW[0]).read(ctx) == pytest.approx(pct)
    assert _reader(NEW[1]).read(ctx) == pytest.approx(busy)


@pytest.mark.parametrize("cell", ["rb4m_write", "rb4m_seqread",
                                  "rb64k_write"])
def test_tiny_objects_stay_on_the_loop(cell, tmp_path):
    """64 KiB objects (the tiny size, and `rb64k_write`'s own): an op's
    frames are under the worker's line and both metrics are on the line
    of a traced run, at 0.0 for reads; the tiny write cells' 32 KiB
    shards reach the line only where sixteen or more sub-ops to one peer
    ride one batch envelope, a few bytes in a hundred."""
    done, _cell = _tiny(cell, trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    for n in NEW:
        got = line["metrics"][n]
        assert got["unit"] == "%"
        assert got["value"] == 0.0 if cell == "rb4m_seqread" \
            else 0.0 <= got["value"] < 15.0


@pytest.mark.parametrize("cell", ["rb4m_write", "rb4m_seqread"])
def test_large_objects_leave_the_socket_on_the_worker(cell, tmp_path):
    """4 MiB objects on 2+1: the client's frame and the 2 MiB shards are
    over the line, so most bytes are the worker's and its thread was
    busy; the run is `correct` (every crc the worker wrote was checked
    by whoever received the frame)."""
    from ceph_tpu.msg import rxworker
    if not rxworker.available():
        pytest.skip("the native library is not built here")
    seconds = 0.6
    cell = shrink(harness.load_cell(cell), seconds)
    cell.config = dict(cell.config, object_size=4 * MIB)
    done = run_tiny(cell, True, (), seconds, tmp_path)
    line = done["result"]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 50.0 < m["msgr_tx_worker_pct"] <= 100.0
    assert 0.0 < m["msgr_tx_worker_busy_pct"] < 100.0 * rxworker.WORKERS
    assert m["msgr_tx_direct_pct"] > 90.0
    assert m["msgr_rx_worker_pct"] > 50.0
    assert not rxworker.running()       # the cluster is down: so is it
