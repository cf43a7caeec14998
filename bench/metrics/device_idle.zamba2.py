"""Share of the traced stretch in which no op ran on the device, in %:
1 - busy/window from the profiler trace, averaged over the chips."""

from bench.lib.readers import idle_percent


def read(records):
    return idle_percent(records)
