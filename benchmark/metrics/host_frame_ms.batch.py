"""Device-idle time under the program's ``tempo.frame`` spans (pandas
frames built: ``TSDF``'s index reset, rows reordered to the layout, the
output frame), per completed pipeline, in ms (program_spans.py)."""

import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "tempo.frame")
