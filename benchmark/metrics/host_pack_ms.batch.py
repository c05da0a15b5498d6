"""Device-idle time under the program's ``tempo.pack`` spans (columns
scattered into [series, time] planes, masks, rebased seconds, window
row bounds, the join's validity planes and chunk layout), per completed
pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.pack")
