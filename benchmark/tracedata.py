"""From a ``jax.profiler`` trace to numbers: the reduction, kept as code.

``extract`` reads an ``.xplane.pb`` with nothing but JAX and keeps a neutral
form — per device plane, per line, ``[name, start_ns, duration_ns]`` — plus
the harness's own sync annotation from the host planes (the trace clock
starts at 0 when the trace starts; the sync event ties it to the host's
``perf_counter``). Every reduction below works on that form, so the small
recorded trace beside this file (``fixtures/``) checks the same code that
reads a chip run (``rehearse.py``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

SYNC = "bench.sync"
# the line of a device plane that holds one event per executed operation;
# the others ("XLA Modules", "Async XLA Ops", ...) cover the same time again
OP_LINE = "XLA Ops"


def extract(log_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = {"planes": [], "sync_ns": None,
           "bytes": os.path.getsize(paths[-1])}
    for pl in pd.planes:
        dev = pl.name.startswith("/device:")
        lines = []
        for ln in pl.lines:
            if dev:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in ln.events]
                lines.append({"name": ln.name, "events": evs})
            elif out["sync_ns"] is None:
                for e in ln.events:
                    if e.name == SYNC:
                        out["sync_ns"] = float(e.start_ns)
                        break
        if dev:
            out["planes"].append({"name": pl.name, "lines": lines})
    return out


def device_planes(tr: dict) -> list[dict]:
    """Planes of chips (``/device:TPU:n``), not ``/device:CUSTOM:...``."""
    return [p for p in tr["planes"] if re.fullmatch(r"/device:TPU:\d+",
                                                    p["name"])]


def op_events(plane: dict) -> list[list]:
    return sorted((e for ln in plane["lines"] if ln["name"] == OP_LINE
                   for e in ln["events"] if e[2] > 0), key=lambda e: e[1])


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(tr: dict, t0_ns: float, t1_ns: float) -> float:
    """Seconds in which an operation ran, averaged over the chips' planes."""
    planes = device_planes(tr)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        iv = union((max(s, t0_ns), min(s + d, t1_ns))
                   for _n, s, d in op_events(p) if s + d > t0_ns and s < t1_ns)
        total += sum(b - a for a, b in iv)
    return total / len(planes) / 1e9


def short_name(hlo: str) -> str:
    """An event is named by its whole HLO text; keep the instruction's
    name, result shape and operation, and a custom call's target."""
    m = re.match(r"(%\S+) = (\(?[a-z0-9]+\[[0-9,]*\])\S*.*? ([a-z\-]+)\(", hlo)
    head = f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else hlo[:60]
    t = re.search(r'custom_call_target="([^"]+)"', hlo)
    if t:
        head += f" {t.group(1)}"
    kind = re.search(r"kind=(k\w+)", hlo)
    return head + (f" {kind.group(1)}" if kind else "")


def top_ops(tr: dict, t0_ns: float, t1_ns: float, k: int = 10) -> list[list]:
    acc: dict[str, float] = {}
    for p in device_planes(tr):
        for n, s, d in op_events(p):
            if t0_ns <= s < t1_ns:
                n = short_name(n)
                acc[n] = acc.get(n, 0.0) + d
    n_planes = max(1, len(device_planes(tr)))
    return [[n, d / n_planes / 1e9] for n, d in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def _gaps(plane: dict, t0_ns: float, t1_ns: float,
          also_busy=()) -> list[tuple[float, float]]:
    """Intervals of [t0, t1] in which the chip's op line shows no event
    (``also_busy``: intervals counted as covered)."""
    iv = union([(s, s + d) for _n, s, d in op_events(plane)
                if s + d > t0_ns and s < t1_ns] + list(also_busy))
    gaps, at = [], t0_ns
    for a, b in iv + [(t1_ns, t1_ns)]:
        if a > at:
            gaps.append((at, min(a, t1_ns)))
        at = max(at, b)
    return gaps


def event_counts(tr: dict) -> list[int]:
    """Operation events per chip plane."""
    return [len(op_events(p)) for p in device_planes(tr)]


def holes(tr: dict, t0_ns: float, t1_ns: float, proofs,
          margin_ns: float = 5e6, need: int = 2) -> list[tuple[float, float]]:
    """Stretches of the trace in which a chip's events are LOST, not absent:
    gaps of a chip's op line that wholly hold ``need`` or more of
    ``proofs`` — intervals ``(start_ns, end_ns)`` on the trace clock inside
    each of which the program ran a device operation on every chip of the
    cell (a query that executed a kernel, from its span's start to its
    end). ``margin_ns`` is what the two clocks may be apart. The union over
    the chips: what is lost on one chip is not read on any."""
    proofs = sorted(proofs)
    if len(proofs) < need:
        return []
    starts = [s for s, _e in proofs]
    shortest = min(e - s for s, e in proofs) + 2 * margin_ns
    found = []
    for p in device_planes(tr):
        for a, b in _gaps(p, t0_ns, t1_ns):
            if b - a < shortest:          # most gaps: between two operations
                continue
            inside = 0
            for s, e in proofs[bisect.bisect_left(starts, a + margin_ns):]:
                if s > b - margin_ns or inside >= need:
                    break
                inside += e <= b - margin_ns
            if inside >= need:
                found.append((a, b))
    return union(found)


def without(tr: dict, cuts) -> dict:
    """The trace with every chip's events that touch a cut taken out."""
    if not cuts:
        return tr

    def keep(e):
        return not any(e[1] < b and e[1] + e[2] > a for a, b in cuts)
    planes = [{"name": p["name"],
               "lines": [{"name": ln["name"],
                          "events": [e for e in ln["events"] if keep(e)]}
                         for ln in p["lines"]]} for p in tr["planes"]]
    return dict(tr, planes=planes)


def idle_gaps(tr: dict, t0_ns: float, t1_ns: float, host_spans,
              k: int = 10, cuts=()) -> list[list]:
    """The longest idle gaps of the first chip, each named by the host span
    (``[name, start_ns, end_ns]`` on the trace clock) that covers most of
    it; "no span" where the program recorded nothing. ``cuts`` (``holes``)
    are no gaps: nothing is known of them."""
    planes = device_planes(tr)
    if not planes:
        return []
    gaps = _gaps(planes[0], t0_ns, t1_ns, cuts)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        best, cover, best_len = "no span", 0.0, float("inf")
        for name, s, e in host_spans:
            c = min(b, e) - max(a, s)
            # the span that covers the most; of equals, the innermost
            if c > cover * 1.001 or (c > 0 and c >= cover * 0.999
                                     and (e - s) < best_len):
                best, cover, best_len = name, c, e - s
        out.append([best, (b - a) / 1e9])
    return out


def named_events(tr: dict, t0_ns: float, t1_ns: float, patterns) -> list:
    """Device events whose name contains one of ``patterns``, as
    ``[plane, name, start_ns, duration_ns]``: the Pallas kernels'
    (benchmark/peaks.json, "kernel_names")."""
    return [[pi, n, s, d] for pi, p in enumerate(device_planes(tr))
            for n, s, d in op_events(p)
            if t0_ns <= s < t1_ns and any(x in n for x in patterns)]
