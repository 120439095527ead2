"""The comparison that decides ``correct``.

Numbers compared, each with a limit of its own (PERF.md §2 gives the
readings each was set from):

- ``answers_err``: over a seeded sample of the answers the timed window
  produced (every query text in it, the longest range among them), the
  worst ``|got - want| / (atol + rtol |want|)`` against the plain f64
  reference; a missing or extra series or step counts as infinite. Limit 1:
  the deployment's stated exactness (rtol 2e-4, atol 1e-4).
- ``readback_abs``: the scrape last acknowledged in the window, read back
  through the data module's seeded raw-selector probes: worst
  ``|got - want|``. Limit 0.
- ``routes_off``: answers of the window whose ``stats.exec_path`` is none
  of the routes the mix expects (or names an interpreted kernel on a TPU).
  Limit 0.

What an answer should be, and what to send to read a scrape back, is the
deployment's data module's (``benchmark/data/``, passed in as ``data``); a
mix's ``ref`` goes to it unread.
"""

from __future__ import annotations

import numpy as np

from . import served

SAMPLE = 4


def err_ratio(got: dict, want: dict, rtol: float, atol: float) -> float:
    if set(got) != set(want):
        return float("inf")
    worst = 0.0
    for k, w in want.items():
        g = got[k]
        if g.shape != w.shape or (np.isfinite(g) != np.isfinite(w)).any():
            return float("inf")
        m = np.isfinite(w)
        if m.any():
            worst = max(worst, float(np.max(
                np.abs(g[m] - w[m]) / (atol + rtol * np.abs(w[m])))))
    return worst


def sample_answers(records: list, seed: int, k: int = SAMPLE) -> list:
    """A seeded sample of kept answers: one of every query text first (of
    those, the longest range first), then more up to ``k``."""
    kept = [r for r in records if r["ok"] and r["body"] is not None]
    if not kept:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0DE])
    order = [kept[i] for i in rng.permutation(len(kept))]
    longest = max(order, key=lambda r: r["req"].end_ms - r["req"].start_ms)
    picked, seen = [longest], {longest["req"].qi}
    for r in order:
        if r["req"].qi not in seen:
            seen.add(r["req"].qi)
            picked.append(r)
    for r in order:
        if len(picked) >= k:
            break
        if not any(r is p for p in picked):
            picked.append(r)
    return picked


def check_answers(picked: list, mix: dict, deploy: dict, seed: int, sids,
                  head_col: int, data) -> tuple[float, list]:
    g = deploy["guarantees"]
    worst, lines = 0.0, []
    for r in picked:
        req = r["req"]
        out_ts = req.out_ts()
        want = data.evaluate(seed, sids, mix["queries"][req.qi]["ref"],
                             out_ts, deploy, head_col)
        got = served.answer_rows(r["body"], out_ts, req.step_ms)
        e = err_ratio(got, want, g["rtol"], g["atol"])
        worst = max(worst, e)
        lines.append(f"{req.promql} [{(req.end_ms - req.start_ms) // 1000}s/"
                     f"{req.step_ms // 1000}s end-{(r['head_ms'] - req.end_ms) // 1000}s] "
                     f"route={r['path']} series={len(got)} err={e:.4g}")
    return worst, lines


def readback(port: int, dataset: str, deploy: dict, seed: int, rec: dict,
             n: int = 2) -> tuple[float, list]:
    """``rec``: the scraper's record of the last container that landed."""
    w = rec["writer"]
    ids = w.ids[rec["row"]:rec["row"] + rec["rows"]]
    worst, lines = 0.0, []
    for p in w.data.probes(seed, ids, rec["col"], deploy, n):
        r = served.query_range(port, dataset, p["promql"], p["start_ms"],
                               p["end_ms"], p["step_ms"])
        if r["code"] != 200:
            return float("inf"), [f"read-back of {p['promql']}: HTTP "
                                  f"{r['code']}"]
        out_ts = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
        got = [(set(k), v) for k, v in served.answer_rows(
            r["body"], out_ts, p["step_ms"]).items()]
        e = 0.0
        for labels, want in p["want"]:
            mine = [v for k, v in got if labels.items() <= k]
            if len(mine) != 1:
                return float("inf"), [
                    f"read-back of {p['promql']}: {len(mine)} series with "
                    f"{labels} among {len(got)} returned"]
            d = np.abs(mine[0] - want)
            e = max(e, float(d.max()) if np.isfinite(d).all()
                    else float("inf"))
        worst = max(worst, e)
        lines.append(f"{p['promql']} stamps {p['start_ms']}..{p['end_ms']}: "
                     f"{len(p['want'])} series, |got-want| max {e:g}")
    return worst, lines


def routes_off(records: list, expect: list[str],
               allow_interpret: bool) -> tuple[int, dict]:
    """(answers on an unexpected route, {route with numbers masked: count})."""
    import re
    off, seen = 0, {}
    for r in records:
        if not r["ok"]:
            continue
        p = r["path"] or ""
        fam = re.sub(r"\d+", "#", p)
        seen[fam] = seen.get(fam, 0) + 1
        good = any(x in p for x in expect)
        if "interpret" in p and not allow_interpret:
            good = False
        off += not good
    return off, seen
