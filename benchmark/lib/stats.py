"""Order statistics over all samples of a window (no chunking)."""


def percentile(values, q):
    """Linear-interpolated q-th percentile (0..100) of ``values``."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
