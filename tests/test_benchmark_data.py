"""The benchmark's data modules, guarded by tier-1.

``benchmark/tests/`` holds the data modules' own cases — ``counter``'s golden
parity, the loader, the open harness (``test_data.py``) and ``hist``'s
golden parity, generator, reference, fill and files (``test_hist_data.py``)
and ``prom``'s — the stamp law, the true-stamp reference, the fill of a
line store, probes, reader, control and files (``test_prom_data.py``) —
and ``prom_miss``'s: the miss law, the reference over the samples that
exist, the fill of a hole store, probes, reader, control and files
(``test_prom_miss_data.py``), every case under a name of its own.
They run in seconds on the CPU, and what they pin is the yardstick: tier-1
collects them here, under their own names, so that the floor counts them.
"""

import pytest

for _mod in ("benchmark.tests.test_data", "benchmark.tests.test_hist_data",
             "benchmark.tests.test_prom_data",
             "benchmark.tests.test_prom_miss_data"):
    pytest.register_assert_rewrite(_mod)

from benchmark.tests.test_data import *        # noqa: E402,F401,F403
from benchmark.tests.test_hist_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_prom_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_prom_miss_data import *   # noqa: E402,F401,F403


# Two cases of those files that a star import alone does not give tier-1:

from benchmark.tests import test_hist_data as _hist_cases   # noqa: E402
from benchmark.tests import test_prom_data as _prom_cases   # noqa: E402

# ``hist``'s fill case bears the name of ``prom``'s, which the later import
# shadows: collected here under a name of its own
test_hist_fill_leaves_the_store_the_write_path_would = \
    _hist_cases.test_fill_leaves_the_store_the_write_path_would


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_prom_data.py pins adhoc_prom to the END of the "
    "workloads lists of query_p50_ms, kernel_roofline_pct and leaf_ms, "
    "demoted_rows_pct's list to adhoc_prom alone and demoted_rows_pct to "
    "the end of per_layer; PR 35 appended adhoc_prom_miss and "
    "hole_cells_pct, as ISSUE 35 asks, and may edit no file the benchmark "
    "has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); the rest of what it says of "
    "promdev_prom_1m is held by test_prom_miss_configuration_and_cell_"
    "are_as_named"))
def test_the_configuration_and_the_cell_are_as_named():
    _prom_cases.test_the_configuration_and_the_cell_are_as_named()
