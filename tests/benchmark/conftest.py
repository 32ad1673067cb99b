"""One assertion of an accepted test counts the benchmark's cells as PR 28
left them (four, the offload cell last). PR 32 adds a fifth and may edit
no file the benchmark has, so the count is marked here as expected to fail
and ``test_bench_autosave.py`` asserts what it stood for (every cell, the
offload cell among them, resolves to its own runner) from the manifest. A
``benchmark`` PR should change the assertion and delete this file."""

import pytest

STALE = "test_bench_offload.py::" \
    "test_dry_resolves_the_offload_cell_to_its_own_runner"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                reason="counts four cells; BENCHMARK.json has five since "
                       "PR 32", strict=True))
