"""``benchmark/tests/test_tsbs_layers.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``)."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_tsbs_layers")

from benchmark.tests.test_tsbs_layers import *     # noqa: E402,F401,F403
