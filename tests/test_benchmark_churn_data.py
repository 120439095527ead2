"""``benchmark/tests/test_churn_data.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_churn_data")

from benchmark.tests.test_churn_data import *     # noqa: E402,F401,F403
