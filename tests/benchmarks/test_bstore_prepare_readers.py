"""`bstore_key_encodes_per_op` and `bstore_csum_reused_pct` (PR 47:
BlueStore's prepare remembers each id's key, and a whole-object write's
extents keep the checksums the write arrived with): the entries, the
readers on hand-made `bstore_txc` spans, and which cells load them."""
from __future__ import annotations

import types

import pytest

from benchmarks import harness
from tests.benchmarks.test_benchmarks import BENCH, CELLS, ROOT

CELL = "rb4m_bluestore_write"
ENTRIES = {
    "bstore_key_encodes_per_op": {
        "name": "bstore_key_encodes_per_op", "unit": "count/op",
        "better": "lower", "source": "program_span", "layer": "objectstore",
        "moves": "ops_s", "workloads": [CELL]},
    "bstore_csum_reused_pct": {
        "name": "bstore_csum_reused_pct", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "objectstore", "moves": "ops_s",
        "workloads": [CELL]},
}
SHARD = 524288


def _read(name, txcs, ops=2):
    mod = harness._load_module(ROOT, "layer_metrics", name)
    return mod.read(types.SimpleNamespace(
        spans={"bstore_txc": txcs} if txcs is not None else {}, ops=ops))


def _txc(nbytes=0, encodes=None, reused=None, **more):
    """A transaction context's span as `BlueStore._deliver` records it;
    the parent's has neither of the two tags."""
    tags = {"prepare_us": 600.0 if nbytes else 60.0, "queued_us": 900.0,
            "block_sync_us": 2000.0, "kv_submit_us": 1500.0,
            "deliver_us": 700.0, "block_write_us": 300.0, "ops": 3,
            "bytes": nbytes, "by_ref_bytes": nbytes, "group": 1,
            "ran_ahead": False, **more}
    if encodes is not None:
        tags["key_encodes"] = encodes
    if reused is not None:
        tags["csum_reused_bytes"] = reused
    return {"name": "bstore_txc", "duration_us": 5700.0, "tags": tags}


@pytest.mark.parametrize("name", ENTRIES)
def test_the_entries_stand_after_pr_46s(name):
    """Appended after `bstore_block_write_ms`, found by name; a later
    PR's come after them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(name)
    assert BENCH["per_layer"][at] == ENTRIES[name]
    assert at > names.index("bstore_block_write_ms")
    mod = harness._load_module(ROOT, "layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        name, ENTRIES[name]["unit"], "objectstore", "ops_s")


DATA, META = (SHARD, 1, SHARD), (0, 0, 0)


@pytest.mark.parametrize("case,txcs,ops,want", [
    # a new object a shard and nothing for its PG-log transaction
    ("one_a_shard", [_txc(*DATA), _txc(*META)] * 22, 2, 11.0),
    ("the_probes_in_front", [_txc(SHARD, 3, SHARD), _txc(0, 1, 0)], 1, 4.0),
    ("warm", [_txc(0, 0, 0)] * 5, 5, 0.0),
    # the program before: no tag, nothing to read
    ("no_tag", [_txc(SHARD), _txc()], 2, None),
    ("mixed", [_txc(SHARD), _txc(SHARD, 6, SHARD)], 2, 3.0),
    ("no_ops", [_txc(*DATA)], 0, None),
    ("no_txcs", [], 2, None),
    ("no_such_span", None, 2, None),
    ("not_a_context", [{"name": "bstore_txc", "duration_us": 1.0,
                        "tags": {"key_encodes": 9}}], 1, None),
])
def test_key_encodes_per_op_on_hand_made_spans(case, txcs, ops, want):
    got = _read("bstore_key_encodes_per_op", txcs, ops)
    assert got == (pytest.approx(want) if want is not None else None)
    if want == 0.0:
        assert got is not None


@pytest.mark.parametrize("case,txcs,want", [
    ("every_shard_whole", [_txc(*DATA), _txc(*META)] * 11, 100.0),
    # a partial write's extent is computed: half the staged bytes
    ("half", [_txc(*DATA), _txc(SHARD, 0, 0)], 50.0),
    ("three_quarters", [_txc(3 * SHARD, 1, 3 * SHARD), _txc(SHARD, 0, 0)],
     75.0),
    # the tag is there and no write brought checksums: a reading, 0
    ("none_reused", [_txc(SHARD, 1, 0), _txc(SHARD, 1, 0)], 0.0),
    # nothing staged for the block file (every object fit its onode,
    # as in a tiny run): the tag is there, a reading, 0
    ("nothing_staged", [_txc(*META), _txc(*META)], 0.0),
    ("no_tag", [_txc(SHARD), _txc()], None),
    ("mixed", [_txc(SHARD), _txc(*DATA)], 100.0),
    ("no_txcs", [], None),
    ("no_such_span", None, None),
])
def test_csum_reused_pct_on_hand_made_spans(case, txcs, want):
    got = _read("bstore_csum_reused_pct", txcs)
    assert got == (pytest.approx(want) if want is not None else None)
    if want == 0.0:
        assert got is not None and isinstance(got, float)


@pytest.mark.parametrize("cell", CELLS)
def test_only_the_bluestore_cell_loads_them(cell):
    loaded = [r.NAME for r in harness.load_cell(cell).readers]
    for name in ENTRIES:
        assert (name in loaded) == (cell == CELL)
        if cell == CELL:
            assert loaded.index(name) > loaded.index("bstore_block_write_ms")
