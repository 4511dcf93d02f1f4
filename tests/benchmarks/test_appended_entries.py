"""The proof that the benchmark takes a new entry: a later PR appends a
configuration, a cell and per-layer entries **at the end** of
BENCHMARK.json and adds their files, and edits nothing. Every file's
structural assertions are a function of `bench` (`entries_stand`, and
`test_benchmarks.contract_holds`); here each is called on a copy with
such entries appended: a configuration on `bluestore` with a
`mon_config`, a cell whose traffic has `events`, and two readers, one
that lists the new cell and one that lists none (so every cell loads
it). An assertion that finds its entries by a position from the end,
counts a list, or pins the whole of a `workloads` list that a later
cell may join, fails here before it fails that PR."""
from __future__ import annotations

import pytest

from tests.benchmarks import (test_acks_carried, test_degraded,
                              test_fastread_cell, test_loop_account,
                              test_loop_parts, test_msgr_ctrl, test_msgr_rx,
                              test_msgr_tx, test_recovery_cell,
                              test_scrub_cell, test_store_direct)
from tests.benchmarks.test_benchmarks import (BENCH, appended_copy,
                                              contract_holds)

#: files whose entries are looked up in `bench` alone, and those that
#: also load cells from the copy's files
BY_BENCH = [test_acks_carried, test_loop_account, test_loop_parts,
            test_msgr_ctrl, test_msgr_rx, test_msgr_tx, test_store_direct]
BY_BENCH_AND_ROOT = [test_degraded, test_fastread_cell, test_recovery_cell,
                     test_scrub_cell]


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    return appended_copy(tmp_path_factory.mktemp("appended"))


def test_the_copy_has_the_accepted_entries_first_and_four_more(appended):
    _root, bench = appended
    for key, more in (("configs", 1), ("workloads", 1), ("per_layer", 2)):
        assert bench[key][:-more] == BENCH[key]
    assert bench["end_to_end"] == BENCH["end_to_end"]


def test_the_contract_holds_with_entries_appended(appended):
    root, bench = appended
    contract_holds(bench, root=root)


@pytest.mark.parametrize(
    "module", BY_BENCH + BY_BENCH_AND_ROOT,
    ids=lambda m: m.__name__.rpartition(".")[2])
def test_a_files_entries_stand_with_entries_appended(module, appended):
    root, bench = appended
    if module in BY_BENCH_AND_ROOT:
        module.entries_stand(bench, root=root)
    else:
        module.entries_stand(bench)
