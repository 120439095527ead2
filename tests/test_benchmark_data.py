"""The benchmark's data modules, guarded by tier-1.

``benchmark/tests/`` holds the data modules' own cases. They run in seconds
on the CPU, and what they pin is the yardstick, so tier-1 collects them,
EACH MODULE IN A FILE OF ITS OWN: ``tests/test_benchmark_<module>.py`` star-
imports ``benchmark/tests/test_<module>.py`` and nothing else, so that a
case (or a fixture) of one module cannot replace another's of the same name
— one namespace for all of them lost ``hist``'s fill case to ``prom``'s
until it was renamed by hand. A new module under ``benchmark/tests/`` gets a
new thin file. This one is ``counter``'s golden parity, the loader and the
open harness (``test_data.py``); the others:

    hist_data           golden parity, generator, reference, fill, files
    prom_data           stamp law, true-stamp reference, line-store fill,
                        probes, reader, control, files
    prom_miss_data      miss law, reference over the samples that exist,
                        hole-store fill, probes, reader, control, files
    wait_layers         the five readers of what a worker waits for (PR 39)
    fall_layer          ``fall_tiles_pct``'s reader (PR 40)
    tsbs_data           walk and tags, reference against its brute-force
                        twin, fill, served path, probes, traffic file, files
    tsbs_layers         the four readers of what a narrow leaf does (PR 41)
    gather_fused_layer  how often a gathered leaf ran as one program (PR 42)
    tsbs_d8_data        the delta8 store's fill, checks, 12 h reference,
                        served path, probes, files, flush readers (PR 44)

Five cases of those modules pin the TAIL of a list in ``BENCHMARK.json``
that later PRs appended to; no PR but a `benchmark` one may edit them, so
their thin files mark them ``xfail(strict=True)`` with the reason and hold
what else they say by membership (ROADMAP.md queue 2 item 0 (12)).
"""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_data")

from benchmark.tests.test_data import *        # noqa: E402,F401,F403
