"""The comparison that decides ``correct``.

Numbers compared, each with a limit of its own (PERF.md §2 gives the
readings each was set from):

- ``answers_err``: over a seeded sample of the answers the timed window
  produced (every query text in it, the longest range among them), the
  worst ``|got - want| / (atol + rtol |want|)`` against the plain f64
  reference; a missing or extra series or step counts as infinite. Limit 1:
  the deployment's stated exactness (rtol 2e-4, atol 1e-4).
- ``readback_abs``: the scrape last acknowledged in the window, read back
  through a raw selector for seeded racks: worst ``|got - want|``. Limit 0.
- ``routes_off``: answers of the window whose ``stats.exec_path`` is none
  of the routes the mix expects (or names an interpreted kernel on a TPU).
  Limit 0.
"""

from __future__ import annotations

import numpy as np

from . import datagen, reference, served

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
                  head_col: int) -> tuple[float, list]:
    g = deploy["guarantees"]
    iv = int(deploy["scrape_interval_ms"])
    worst, lines = 0.0, []
    for r in picked:
        req = r["req"]
        out_ts = req.out_ts()
        want = reference.evaluate(seed, sids, mix["queries"][req.qi]["ref"],
                                  out_ts, iv, head_col,
                                  int(deploy["labels"]["groups"]))
        got = served.answer_rows(r["body"], out_ts, req.step_ms)
        e = err_ratio(got, want, g["rtol"], g["atol"])
        worst = max(worst, e)
        lines.append(f"{req.promql} [{(req.end_ms - req.start_ms) // 1000}s/"
                     f"{req.step_ms // 1000}s end-{(r['head_ms'] - req.end_ms) // 1000}s] "
                     f"route={r['path']} series={len(got)} err={e:.4g}")
    return worst, lines


def readback(port: int, dataset: str, deploy: dict, seed: int, rec: dict,
             n_racks: int = 2) -> tuple[float, list]:
    """``rec``: the scraper's record of the last container that landed."""
    iv = int(deploy["scrape_interval_ms"])
    per = int(deploy["labels"]["per_rack"])
    w = rec["writer"]
    lo = rec["row"]
    ids = w.ids[lo:lo + rec["rows"]]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x4EAD])
    worst, lines = 0.0, []
    cols = np.arange(rec["col"] - 3, rec["col"] + 1)
    out_ts = datagen.BASE_TS + cols * iv
    for sid in rng.choice(ids, n_racks, replace=False):
        rack = int(sid) // per
        r = served.query_range(port, dataset,
                               f'{deploy["metric"]}{{rack="r{rack}"}}',
                               int(out_ts[0]), int(out_ts[-1]), iv)
        if r["code"] != 200:
            return float("inf"), [f"read-back of rack r{rack}: HTTP {r['code']}"]
        got = served.answer_rows(r["body"], out_ts, iv)
        mine = {int(dict(k)["host"][1:]): v for k, v in got.items()}
        # of the rack's series, those in this container (on a mesh the
        # others live on other shards, whose containers of this scrape may
        # not have been sent yet)
        want_ids = np.intersect1d(np.arange(rack * per, rack * per + per),
                                  ids).tolist()
        if not set(want_ids) <= set(mine):
            return float("inf"), [f"read-back of rack r{rack}: series "
                                  f"{sorted(mine)}, expected {want_ids}"]
        want = reference.raw_values(seed, want_ids, cols)
        gotm = np.stack([mine[i] for i in want_ids])
        d = np.abs(gotm - want)
        e = float("inf") if not np.isfinite(d).all() else float(d.max())
        worst = max(worst, e)
        lines.append(f'{deploy["metric"]}{{rack="r{rack}"}} columns '
                     f"{cols[0]}..{cols[-1]}: {len(want_ids)} series, "
                     f"|got-want| max {e:g}")
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
