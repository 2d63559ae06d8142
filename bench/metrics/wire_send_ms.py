"""wire_send_ms (ms; frontend + wire): mean time per response to write it
to the socket and drain it: the header join and ``writer.write``
(program span ``wire_send``) plus ``await drain()`` (``wire_drain``), over
the responses sent."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ["wire_send", "wire_drain"],
                                 "wire_send")
