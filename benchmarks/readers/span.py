"""One statistic of one of the program's host spans, from the roll-up its
``SpanRecorder`` gives (``count``, ``total_s``, ``mean_s``, ``p50_s``,
``p99_s``), taken at the end of the window."""


def read(obs, *, span: str, stat: str = "p50_s", scale: float = 1.0):
    rollup = obs["spans"].get(span)
    return None if not rollup else rollup[stat] * scale
