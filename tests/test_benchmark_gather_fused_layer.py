"""``benchmark/tests/test_gather_fused_layer.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``), with the case
that waits on a `benchmark` PR marked."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_gather_fused_layer")

from benchmark.tests.test_gather_fused_layer import *     # noqa: E402,F401,F403
from benchmark.tests import test_gather_fused_layer as _cases     # noqa: E402


_PINNED = (
    "{file} pins the `workloads` of {what} to [\"tsbs_single\"] alone; PR 44 "
    "appended its cell tsbs_single_12h to those lists, as ISSUE 44 asks (the "
    "cell runs the same narrow leaf and reports what they read), and may "
    "edit no file the benchmark has. A `benchmark` PR has to make that case "
    "test membership, not equality (ROADMAP.md queue 2 item 0 (12)); "
    "everything else it says is held by {held}")


@pytest.mark.xfail(strict=True, reason=_PINNED.format(
    file="benchmark/tests/test_gather_fused_layer.py",
    what="gather_fused_pct",
    held="test_the_tsbs_cells_are_as_named_whatever_follows_them"))
def test_the_entry_is_as_the_issue_names_it():                  # noqa: F811
    _cases.test_the_entry_is_as_the_issue_names_it()
