"""Nearest-rank percentile (no interpolation), the serving convention
of `obs/metrics.pct_nearest`, copied: conservative at the tail on small
request counts."""


def pct_nearest(vals, q: int):
    s = sorted(vals)
    if not s:
        return None
    return s[min(len(s) - 1, max(0, -(-q * len(s) // 100) - 1))]
