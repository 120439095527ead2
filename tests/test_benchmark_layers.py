"""``benchmark/tests/test_layers.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``), with the case
that waits on a `benchmark` PR marked."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_layers")

from benchmark.tests.test_layers import *     # noqa: E402,F401,F403
from benchmark.tests import test_layers as _cases     # noqa: E402


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_layers.py pins PR 25's readers to the END of "
    "per_layer; every later PR that brought a reader appended its entry "
    "there, as its issue asked, and may edit no file the benchmark has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); each reader's own case here "
    "holds what it reads"))
def test_every_new_metric_of_benchmark_json_has_a_case_here():  # noqa: F811
    _cases.test_every_new_metric_of_benchmark_json_has_a_case_here()
