"""``benchmark/tests/test_prom_data.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``), with the case
that waits on a `benchmark` PR marked."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_prom_data")

from benchmark.tests.test_prom_data import *     # noqa: E402,F401,F403
from benchmark.tests import test_prom_data as _cases     # noqa: E402


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_prom_data.py pins adhoc_prom to the END of the "
    "workloads lists of query_p50_ms, kernel_roofline_pct and leaf_ms, "
    "demoted_rows_pct's list to adhoc_prom alone and demoted_rows_pct to "
    "the end of per_layer; PR 35 appended adhoc_prom_miss and "
    "hole_cells_pct, as ISSUE 35 asks, and may edit no file the benchmark "
    "has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); the rest of what it says of "
    "promdev_prom_1m is held by test_the_prom_cells_are_as_named_whatever_"
    "follows_them"))
def test_the_configuration_and_the_cell_are_as_named():
    _cases.test_the_configuration_and_the_cell_are_as_named()
