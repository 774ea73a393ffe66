"""Statistics over samples, and deltas of the program's log-bucketed
histograms (``parameter_server_tpu/utils/trace.py::LatencyHistogram``:
bucket ``i`` has upper edge ``1e-6 * 1.25**i`` seconds)."""

import math

_BASE, _GROWTH = 1e-6, 1.25


def percentile(values, p):
    """Linear-interpolated percentile (``p`` in 0..100); None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def span_ms(steps, name):
    """Milliseconds of every span ``name`` recorded on ``steps``."""
    return [1e3 * (b - a) for s in steps for n, a, b in s.spans if n == name]


def hist_delta(after, before):
    """``to_dict`` digests of one cumulative histogram -> sparse bucket
    counts recorded between the two."""
    b0 = (before or {}).get("b", {})
    out = {}
    for k, c in (after or {}).get("b", {}).items():
        d = int(c) - int(b0.get(k, 0))
        if d > 0:
            out[int(k)] = d
    return out


def hist_percentile_s(buckets, p):
    """Percentile in seconds from sparse bucket counts, interpolated
    geometrically inside the bucket (the histogram resolves 25 %)."""
    total = sum(buckets.values())
    if not total:
        return None
    target = total * p / 100.0
    cum = 0
    for i in sorted(buckets):
        c = buckets[i]
        if cum + c >= target:
            hi = _BASE * _GROWTH**i
            lo = hi / _GROWTH if i else 0.0
            frac = (target - cum) / c
            return hi * frac if not lo else lo * (hi / lo) ** frac
        cum += c
    return _BASE * _GROWTH ** max(buckets)


def hist_percentile_ms(buckets, p):
    v = hist_percentile_s(buckets, p)
    return None if v is None else 1e3 * v
