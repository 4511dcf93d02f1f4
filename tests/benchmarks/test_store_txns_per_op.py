"""`store_txns_per_op` (PR 50: an EC shard's sub-write is one store
transaction, the PG's log entry and meta ride it): the entry, the reader
on hand-made `store_commit` spans, and which cells load it."""
from __future__ import annotations

import types

import pytest

from benchmarks import harness
from tests.benchmarks.test_benchmarks import BENCH, CELLS, ROOT

NAME = "store_txns_per_op"
#: the cells that write in their window, but `rb4m_recovery_write`: its
#: accepted test pins the accepted entries that list it
#: (`test_recovery_cell.JOINED`), and PERF.md 7 asks a `benchmark` PR
#: to let it join
WRITE_CELLS = ["rb4m_write", "rb4m_bluestore_write", "rb64k_write"]
ENTRY = {"name": NAME, "unit": "txns/op", "better": "lower",
         "source": "program_span", "layer": "objectstore", "moves": "ops_s",
         "workloads": WRITE_CELLS}


def _read(spans, ops):
    mod = harness._load_module(ROOT, "layer_metrics", NAME)
    return mod.read(types.SimpleNamespace(
        spans={"store_commit": spans} if spans is not None else {},
        ops=ops))


def _span(ops=4):
    """A `store_commit` span as `objectstore.store._observed_txn`
    records it round a `queue_transaction`."""
    return {"name": "store_commit", "duration_us": 35.0,
            "tags": {"ops": ops}}


def test_the_entry_stands_after_pr_49s():
    """Appended after `enc_bitmatrix_roofline`, found by name; a later
    PR's come after it."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NAME)
    assert BENCH["per_layer"][at] == ENTRY
    assert at > names.index("enc_bitmatrix_roofline")
    mod = harness._load_module(ROOT, "layer_metrics", NAME)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        NAME, "txns/op", "objectstore", "ops_s")


@pytest.mark.parametrize("case,spans,ops,want", [
    ("no_such_span", None, 3, None),
    ("no_spans", [], 3, None),
    # a shard's data and, after it, its PG-log entry: the program before
    ("a_pair_a_shard", [_span(4), _span(2)] * 11, 1, 22.0),
    # eleven shards with their entries inside and the primary's intent
    ("one_a_shard", [_span(6)] * 11 + [_span(2)], 1, 12.0),
    ("many_ops", [_span()] * 36, 3, 12.0),
    # an empty transaction is one too: the store is called all the same
    ("empty_ones_count", [_span(0)] * 5, 2, 2.5),
    ("no_ops", [_span()] * 22, 0, None),
])
def test_txns_per_op_on_hand_made_spans(case, spans, ops, want):
    got = _read(spans, ops)
    assert got == (pytest.approx(want) if want is not None else None)
    if want is not None:
        assert isinstance(got, float)


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_that_write_in_their_window_load_it(cell):
    loaded = [r.NAME for r in harness.load_cell(cell).readers]
    assert (NAME in loaded) == (cell in WRITE_CELLS)
    if cell in WRITE_CELLS:
        assert loaded.index(NAME) > loaded.index("loop_store_pct")
