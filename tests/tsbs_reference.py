"""Brute-force twin of ``benchmark/data/tsbs_cpu``: TSBS DevOps ``cpu-only``
in plain Python integers, one series and one step at a time.

It imports nothing of the program and nothing of the benchmark, and shares
no line with either: the mixer, the tag draws, the clamped walk and
``agg(fn(metric{hostname=~hosts}[w]))`` are spelled out again, slowly. The
benchmark's reference (numpy, vectorised, walks only the selected series)
is tied to it series by series in ``benchmark/tests/test_tsbs_data.py``; the
served path is compared with it there too.
"""

import math

BASE_TS = 1_700_000_000_000
M1, M2, M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35
U32 = 0xFFFF_FFFF
FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1")
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ENVS = ("production", "staging", "test")
TEAMS = ("SF", "NYC", "LON", "CHI")
# weights of a unit normal rounded to the nearest integer, in 65536ths
STEP_WEIGHTS = {-3: 407, -2: 3971, -1: 15840, 0: 25100, 1: 15840, 2: 3971,
                3: 407}


def fold_seed(seed):
    x = (seed ^ (seed >> 32) ^ 0xA511E9B3) & U32
    x = (x * M2) & U32
    return x ^ (x >> 15)


def mix(word, s, c):
    x = (((s * M1) & U32) ^ word) ^ ((c * M2 + M3) & U32)
    x = (x * M2) & U32
    x ^= x >> 15
    return (x * M3) & U32


def draw(word, host, n):
    return (mix(word, host, 0) >> 8) % n


def labels_of(series):
    """The eleven labels of series ``series``."""
    host, field = divmod(series, 10)
    region = REGIONS[draw(0x7A601, host, 9)]
    return {"_metric_": "cpu_" + FIELDS[field],
            "hostname": "host_%d" % host,
            "region": region,
            "datacenter": region + "abc"[draw(0x7A602, host, 3)],
            "rack": str(draw(0x7A603, host, 100)),
            "os": OSES[draw(0x7A604, host, 3)],
            "arch": ("x64", "x86")[draw(0x7A605, host, 2)],
            "team": TEAMS[draw(0x7A606, host, 4)],
            "service": str(draw(0x7A607, host, 20)),
            "service_version": str(draw(0x7A608, host, 2)),
            "service_environment": ENVS[draw(0x7A609, host, 3)]}


def step(word, series, k):
    h = mix(word, series, k) >> 16
    at = 0
    for d in sorted(STEP_WEIGHTS):
        at += STEP_WEIGHTS[d]
        if h < at:
            return d
    raise AssertionError("the weights add up to 65536")


def walk(seed, series, k_hi):
    """[x(series, 0), ..., x(series, k_hi)] as Python ints."""
    word = fold_seed(seed)
    x = (mix(word, series, U32) >> 8) % 101
    out = [x]
    for k in range(1, k_hi + 1):
        x = min(100, max(0, x + step(word, series, k)))
        out.append(x)
    return out


def stamp(k, iv_ms=10_000):
    return BASE_TS + k * iv_ms


def window_fn(fn, samples):
    if not samples:
        return math.nan
    if fn == "max_over_time":
        return float(max(samples))
    if fn == "min_over_time":
        return float(min(samples))
    if fn == "sum_over_time":
        return float(sum(samples))
    if fn == "avg_over_time":
        return sum(samples) / len(samples)
    if fn == "count_over_time":
        return float(len(samples))
    raise ValueError(fn)


def series_answer(seed, series, fn, window_s, out_ts, head_col, iv_ms=10_000):
    """fn(series[w]) a step: the samples whose stamp lies in the closed
    window [t - w, t], of the scrapes 0..head_col."""
    xs = walk(seed, series, head_col)
    return [window_fn(fn, [xs[k] for k in range(head_col + 1)
                           if t - window_s * 1000 <= stamp(k, iv_ms) <= t])
            for t in out_ts]


def evaluate(seed, n_series, metric, hosts, agg, fn, window_s, out_ts,
             head_col, iv_ms=10_000):
    """agg(fn(metric{hostname=~hosts}[w])) over the series 0..n_series-1,
    one value a step, NaN where no selected series has a sample; None where
    nothing is selected."""
    picked = [s for s in range(n_series)
              if labels_of(s)["_metric_"] == metric
              and labels_of(s)["hostname"] in {"host_%d" % h for h in hosts}]
    if not picked:
        return None
    rows = [series_answer(seed, s, fn, window_s, out_ts, head_col, iv_ms)
            for s in picked]
    out = []
    for j in range(len(out_ts)):
        have = [r[j] for r in rows if not math.isnan(r[j])]
        if not have:
            out.append(math.nan)
        elif agg == "max":
            out.append(max(have))
        elif agg == "min":
            out.append(min(have))
        elif agg == "sum":
            out.append(sum(have))
        elif agg == "avg":
            out.append(sum(have) / len(have))
        elif agg == "count":
            out.append(float(len(have)))
        else:
            raise ValueError(agg)
    return out
