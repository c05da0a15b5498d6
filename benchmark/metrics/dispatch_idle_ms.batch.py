"""Device-idle time under the program's ``tempo.dispatch`` spans, from
an op's first device call to its last blocking fetch, outside the pack,
unpack and frame work nested there: launch latency and the transfers
the host waits on.  Per completed pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.dispatch")
