"""Units per second between the first and the last fence of a series of
``(seconds, units completed)`` pairs. See ``lib.stats.rate_between_fences``."""

from benchmarks.lib.stats import rate_between_fences


def read(obs, *, series: str):
    fences = obs["series"].get(series)
    return rate_between_fences(fences) if fences and len(fences) > 1 else None
