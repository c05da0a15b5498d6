"""The bytes and operations a kernel's work requires, from its shapes.

These counts do not depend on how the program implements the kernel:
every input is read once and every output written once, at the width
its values need.  A reader divides the least time they allow at the
chip's peaks by the device time the trace gives the kernel.
"""

TS_BYTES = 8        # int64 nanosecond timestamps
INDEX_BYTES = 4     # int32 row index per output
VALID_BYTES = 1     # one validity byte per value


def asof_join(n_left: int, n_right: int, n_right_cols: int) -> dict:
    """An AS-OF join of ``n_left`` rows against ``n_right`` rows, both
    already grouped by series and sorted by time, that finds for every
    left row the last right row at or before it for each of
    ``n_right_cols`` right columns (skipping nulls, so one index per
    column).

    Reads each side's timestamps and each right column's validity;
    writes one row index per left row and right column.  Series
    membership is the sorted layout's and costs no bytes.  Operations:
    one compare per merged row and a select per merged row and column.
    Copying the values themselves by those indices is the gather's
    work, not the join's.
    """
    read = (n_left * TS_BYTES + n_right * TS_BYTES
            + n_right * n_right_cols * VALID_BYTES)
    written = n_left * n_right_cols * INDEX_BYTES
    ops = (n_left + n_right) * (1 + n_right_cols)
    return {"bytes": read + written, "ops": ops}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(least seconds, bound): the larger of bytes over HBM bandwidth
    and operations over the bf16 peak, and which of the two it is."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops else (by_ops,
                                                             "compute")
