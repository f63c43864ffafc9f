"""Percent of the lanes the traced chunks' step programs computed (slots x
width x scan steps) that carried a prompt token or a decoding slot's token
or block: the `lanes_valid` and `lanes` ids of their `serve.harvest` spans,
summed (`metrics/_serve_parts.py`)."""
from metrics._serve_parts import lane_valid_share


def read(trace, counters, cell):
    return lane_valid_share(trace, cell)
