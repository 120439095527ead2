#!/usr/bin/env python3
"""One benchmark cell, then the program's own counters of one family.

    python3 scripts/cell_counters.py filodb_query_mesh_prepared \\
        --workload mesh_adhoc --seed 7 --seconds 51 --trace 0

runs ``benchmark/run.py`` with the arguments after the family's name, in this
process (one process holds the chips), and then writes every counter of the
metrics registry whose name starts with the family's to stderr, one a line,
as ``/metrics`` would render it: what a run says through the repo's own
counters where no benchmark file reads them (set-up's queries are in the
counts: the registry is the process's). The cell's result line stays the last
line of stdout, and the exit code is the run's."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    family, rest = argv[0], argv[1:]
    from benchmark import run
    rc = run.main(rest)
    from filodb_tpu.utils.metrics import registry
    for line in registry.expose_prometheus().splitlines():
        if line.startswith(family):
            sys.stderr.write(f"counter: {line}\n")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
