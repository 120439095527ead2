"""The benchmark's data modules, guarded by tier-1.

``benchmark/tests/`` holds the data modules' own cases — ``counter``'s golden
parity, the loader, the open harness (``test_data.py``) and ``hist``'s
golden parity, generator, reference, fill and files (``test_hist_data.py``)
and ``prom``'s — the stamp law, the true-stamp reference, the fill of a
line store, probes, reader, control and files (``test_prom_data.py``).
They run in seconds on the CPU, and what they pin is the yardstick: tier-1
collects them here, under their own names, so that the floor counts them.
"""

import pytest

for _mod in ("benchmark.tests.test_data", "benchmark.tests.test_hist_data",
             "benchmark.tests.test_prom_data"):
    pytest.register_assert_rewrite(_mod)

from benchmark.tests.test_data import *        # noqa: E402,F401,F403
from benchmark.tests.test_hist_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_prom_data import *   # noqa: E402,F401,F403
