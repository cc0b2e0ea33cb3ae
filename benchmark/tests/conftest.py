"""test_benchmark.py runs every cell through broken_run.py, which breaks
``linalg.cg`` and the session's tickets: the timed path of the one-chip
cells, not ``dist_cg``. A PR that adds a cell may not edit that file, so
the four-chip cell's two cases are expected to fail there; the same two
breakages of ``dist_cg`` are test_mesh_cell.py::test_broken_dist_cg_is_not_correct.
(PERF.md section 7: ``break_program`` should take ``dist_cg`` too; then this
file goes.)"""

import pytest


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name.startswith(
                "test_broken_timed_path_is_not_correct[pde_cg_4chip-"):
            item.add_marker(pytest.mark.xfail(
                reason="broken_run.py does not break dist_cg; see "
                       "test_mesh_cell.py", strict=True))
