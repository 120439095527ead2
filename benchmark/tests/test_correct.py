"""``correct`` has to be able to come out false.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (these
are the benchmark's own tests; the repo's tier-1 run does not collect them).

- the control (benchmark/control.py): the reference in the program's place,
  one bf16 pass, at a size a test can hold, fails a number of the cell;
- the harness's own run with the timed path broken underneath — an answer
  altered where it is rendered, a sample altered where it is appended —
  reports ``correct`` false, the look for a chip skipped here.
"""

import argparse
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

STUB = {"platform": "cpu-test", "kind": "TPU v5 lite", "count": 1}
TINY = {"series": 2048}


def _run(seed=11, seconds=3.0):
    from benchmark import run
    args = argparse.Namespace(workload="adhoc_cold", seed=seed,
                              seconds=seconds, trace=0)
    return run.run(args, dict(STUB), allow_interpret=True, shrink=TINY)


def test_control_bf16_reference_fails_both_numbers():
    from benchmark import control
    n = control.numpy_control("adhoc_cold", 2**31 + 5, 4, series=2048)
    assert n["readback_abs"] > 0          # limit 0: not correct
    assert n["answers_err"] > 1           # limit 1: not correct either


def test_sound_run_is_correct():
    res = _run()
    assert res is not None and res["correct"] is True and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert {k: c["limit"] for k, c in res["compared"].items()} == {
        "answers_err": 1.0, "readback_abs": 0.0, "routes_off": 0.0}


def test_answer_altered_where_it_is_rendered(monkeypatch):
    from filodb_tpu.http import api
    real = api.matrix_to_prom_json

    def bent(res):
        out = real(res)
        for s in out.get("result", []):
            if s["values"] and not s["metric"].get("host"):
                t, v = s["values"][-1]
                s["values"][-1] = [t, repr(float(v) * 1.001)]
        return out
    monkeypatch.setattr(api, "matrix_to_prom_json", bent)
    res = _run()
    assert res is not None and res["correct"] is False


def test_sample_altered_where_it_is_appended(monkeypatch):
    from filodb_tpu.core import chunkstore
    real = chunkstore.SeriesStore.append

    def bent(self, part_ids, ts, values):
        import numpy as np
        values = np.asarray(values, np.float64)
        # live scrapes only: registration and the set-up read-back pass
        if len(ts) and int(np.min(ts)) > 1_700_000_000_000 + 720 * 10_000:
            values = values + 1.0
        return real(self, part_ids, ts, values)
    monkeypatch.setattr(chunkstore.SeriesStore, "append", bent)
    res = _run(seconds=4.0)
    assert res is not None and res["correct"] is False


def test_no_tpu_no_line(capsys):
    from benchmark import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "adhoc_cold", "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
