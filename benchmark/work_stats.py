"""The bytes and operations range stats and the exact EMA require, from
their shapes, by ``work.py``'s rule: every input read once and every
output written once, at the width its values need.  A reader divides
the least time they allow at the chip's peaks (``work.least_seconds``)
by the device time the trace gives the op's programs.

The rows are the left rows of a pipeline (``ctx.records``); the columns
come from the cell's traffic mix (``ctx.cell.traffic``).
"""

SECONDS_BYTES = 4   # int32 seconds from the series' start
VALUE_BYTES = 4     # float32 value
VALID_BYTES = 1     # one validity byte per value
STAT_BYTES = 4      # float32 result
RANGE_STATS = 7     # mean, count, min, max, sum, stddev, zscore


def range_stats(n_rows: int, n_cols: int) -> dict:
    """withRangeStats over ``n_rows`` rows and ``n_cols`` columns,
    already grouped by series and sorted by time.

    Reads each row's seconds once, and each column's value and validity
    once; writes the seven float32 stats per row and column.  Which
    rows share a window follows from the seconds and costs no bytes.
    Operations: one per stat written."""
    read = n_rows * (SECONDS_BYTES + n_cols * (VALUE_BYTES + VALID_BYTES))
    written = n_rows * n_cols * RANGE_STATS * STAT_BYTES
    return {"bytes": read + written, "ops": n_rows * n_cols * RANGE_STATS}


def ema(n_rows: int) -> dict:
    """The exact EMA of one column over ``n_rows`` sorted rows: reads
    each value and its validity once, writes one float32 per row; one
    multiply-add per row."""
    return {"bytes": n_rows * (VALUE_BYTES + VALID_BYTES + STAT_BYTES),
            "ops": 2 * n_rows}


def stats_columns(traffic: dict):
    """Columns the mix's ``withRangeStats`` steps summarize together, or
    None where it has none (or leaves the columns to the frame)."""
    n = [len(s.get("args", {}).get("colsToSummarize") or [])
         for s in traffic["pipeline"] if s["op"] == "withRangeStats"]
    return sum(n) or None


def exact_emas(traffic: dict) -> int:
    """The mix's exact ``EMA`` steps."""
    return sum(1 for s in traffic["pipeline"]
               if s["op"] == "EMA" and s.get("args", {}).get("exact"))
