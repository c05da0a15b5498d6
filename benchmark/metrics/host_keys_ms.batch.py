"""Device-idle time under the program's ``tempo.keys`` spans (partition
keys factorized into series ids, timestamps to nanoseconds: packing.py),
per completed pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.keys")
