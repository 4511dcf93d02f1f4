"""The tests that run every cell of BENCHMARK.json through
`test_benchmarks._tiny` shrink it to three OSDs and a 2+1 pool for well
under a second. The recovery deployment cannot be shrunk that way: its
events fall 2 s and 16 s into a window, it needs an OSD to spare for
each position that is marked out, and a 2+1 pool with one of three OSDs
stopped is below `min_size` and takes no write. Its cases of those
tests are skipped here, by name, and `test_recovery_cell.py` runs the
cell tiny at a shape of its own (six OSDs, 2+2, the events scaled) and
asserts the same families on its line. The seam test says in its name
which cells it is about: the ones accepted before any cell used the
schedule.
"""
import pytest

CELL = "rb4m_recovery_write"
TINY_AT_THREE_OSDS = {
    "test_tiny_traced_run_reports_the_acks",
    "test_tiny_traced_run_reports_the_control_frames",
    "test_tiny_traced_run_reports_the_receive_path",
    "test_tiny_traced_run_reports_the_send_path",
    "test_tiny_traced_run_reports_the_stores_share",
    "test_tiny_traced_run_reports_the_family",
    "test_tiny_traced_runs_parts_add_up"}
BEFORE_THE_SCHEDULE = {
    "test_an_accepted_cell_uses_no_seam_but_the_fastread_cells_own"}


def pytest_collection_modifyitems(items):
    for item in items:
        spec = getattr(item, "callspec", None)
        if spec is None or CELL not in spec.params.values():
            continue
        name = item.originalname
        if name in TINY_AT_THREE_OSDS:
            item.add_marker(pytest.mark.skip(
                reason=f"{CELL} cannot run at _tiny's three OSDs and 2+1; "
                       f"test_recovery_cell.py runs it at its own shape"))
        elif name in BEFORE_THE_SCHEDULE:
            item.add_marker(pytest.mark.skip(
                reason=f"{CELL} is the cell that uses the schedule"))
