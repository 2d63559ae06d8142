"""wire_encode_ms (ms; frontend + wire): mean time to encode one evaluate
response to its wire bytes, binary frame or JSON (program span
``wire_encode``, one a response that is encoded: a wire-LRU hit has
none)."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ["wire_encode"], "wire_encode")
