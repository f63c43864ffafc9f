"""Share of the traced window in which no operation ran on the device."""
from metrics._common import idle_share_percent


def read(trace, counters, cell):
    return idle_share_percent(trace)
