"""One statistic, in seconds x ``scale``, of the device time of each
execution of one program in the traced slice: the program whose name matches
``pattern``, or else the one that took most device time (the train step)."""

from benchmarks.lib import xtrace
from benchmarks.lib.stats import percentile


def read(obs, *, pattern=None, q: float = 50.0, scale: float = 1.0):
    if obs.get("trace") is None:
        return None
    durations = xtrace.module_durations(obs["trace"], pattern)
    return percentile(durations, q) * scale if durations else None
