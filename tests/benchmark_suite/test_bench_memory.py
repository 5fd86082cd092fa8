"""``memory_peak_bytes``: the larger of two readings that are each of one
moment; never a sum of peaks from different moments, never cut at a limit."""

from benchmarks.lib.memory import MemoryWatch


class FakeDevice:
    def __init__(self, readings):
        self.readings = list(readings)

    def memory_stats(self):
        return self.readings.pop(0) if len(self.readings) > 1 \
            else self.readings[0]


def stats(in_use, reserved, peak_in_use, peak_reserved, limit=1000):
    return {"bytes_in_use": in_use, "bytes_reserved": reserved,
            "peak_bytes_in_use": peak_in_use,
            "peak_bytes_reserved": peak_reserved, "bytes_limit": limit}


def test_peak_is_the_most_one_reading_held_not_the_sum_of_two_peaks():
    # set-up held 600 of buffers with no scratch; the window holds 300 of
    # buffers and 500 of scratch: the two peaks sum to 1100, over the limit,
    # and the chip never held that
    dev = FakeDevice([stats(300, 500, 600, 500), stats(310, 500, 600, 500),
                      stats(10, 500, 600, 500)])
    watch = MemoryWatch([dev])
    watch.sample()
    watch.sample()
    assert watch.report() == {
        "memory_peak_bytes": 810, "memory_peak_buffers_bytes": 600,
        "memory_held_bytes": 810, "memory_limit_bytes": 1000}


def test_a_buffer_that_lives_inside_a_call_shows_in_the_allocators_peak():
    # the samples at the window's edges saw 400 + 50; inside a call the
    # buffers reached 900: reported as measured, though 900 + 50 was likely
    dev = FakeDevice([stats(400, 50, 900, 120)])
    watch = MemoryWatch([dev])
    watch.sample()
    assert watch.report()["memory_peak_bytes"] == 900


def test_the_fullest_chip_is_reported_and_a_cpu_reports_nothing():
    watch = MemoryWatch([FakeDevice([stats(100, 0, 100, 0)]),
                         FakeDevice([stats(700, 10, 700, 10)])])
    assert watch.report()["memory_peak_bytes"] == 710

    class Cpu:
        def memory_stats(self):
            return None
    assert MemoryWatch([Cpu()]).report() is None
