"""``benchmark/tests/test_correct.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_correct")

from benchmark.tests.test_correct import *     # noqa: E402,F401,F403
