"""Device-idle time under the program's ``tempo.layout`` spans (the
(key, ts) sort of a frame or join side and its takes: packing.py), per
completed pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.layout")
