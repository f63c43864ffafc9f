"""Compile requests the process made inside the measured window (the
program's compile_report(), after minus before): should read 0."""


def read(trace, counters, cell):
    return counters.get("compile_requests_in_window")
