"""A percentile of one of the benchmark's own series of samples (times on
the benchmark's clock, one per request or per token)."""

from benchmarks.lib.stats import percentile


def read(obs, *, series: str, q: float):
    samples = obs["series"].get(series)
    return percentile(samples, q) if samples else None
