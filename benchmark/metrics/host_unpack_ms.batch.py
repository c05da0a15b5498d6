"""Device-idle time under the program's ``tempo.unpack`` spans (planes
gathered back to rows, the join's right-row indices and column
gathers), per completed pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.unpack")
