"""Frame-level AS-OF join: packing, prefixing, skew bucketing, assembly.

Reference behaviour being reproduced (python/tempo/tsdf.py:463-560):

* column prefixing of non-partition columns on both sides (tsdf.py:77-94,
  529-531), ``right_prefix`` defaulting to ``"right"``;
* ``skipNulls`` / sequence-number tie-break / suppress_null_warning
  semantics via the kernels in ``tempo_tpu.ops.asof``;
* the skew variant (``tsPartitionVal``/``fraction``): overlapping
  time-bucket partitions (tsdf.py:164-190) - here realised by composing
  the partition key with a time-bracket id and replicating the trailing
  ``fraction`` of each right bracket into the next one, which bounds the
  padded series length (the packed-layout analog of Spark skew
  mitigation) and doubles as the halo pattern used for time-sharded
  series (SURVEY.md section 2.3);
* the ``sql_join_opt`` broadcast fast path (tsdf.py:482-509): taken when
  either side's estimated in-memory size is under 30MiB; its observable
  difference - it is an *inner* range join, so left rows with no
  preceding right row are dropped - is preserved;
* per-column missing-lookback warnings for the skew path
  (tsdf.py:150-159);
* Scala's ``maxLookback`` row cap on the merged stream
  (scala/.../asofJoin.scala:64-88), exposed as a keyword.
"""

from __future__ import annotations

import logging
from typing import List, Optional

import numpy as np
import pandas as pd
from pandas._libs.lib import is_range_indexer

from tempo_tpu import packing, profiling, resilience
from tempo_tpu.profiling import span
from tempo_tpu.ops import asof as asof_ops

logger = logging.getLogger(__name__)


def _estimate_merged_lanes(l_codes: np.ndarray, r_codes: np.ndarray,
                           n_series: int) -> int:
    """Padded merged-lane count the AS-OF kernels would materialise for
    the dense layout — the quantity whose measured ceiling (~205K lanes,
    round-3 chip notes) OOM-kills the XLA compiler.  Host-side and O(n):
    runs before any packing so oversize joins can be rerouted."""
    max_l = int(np.bincount(l_codes, minlength=max(n_series, 1)).max(initial=0))
    max_r = int(np.bincount(r_codes, minlength=max(n_series, 1)).max(initial=0))
    return packing.pad_length(max_l) + packing.pad_length(max_r)


def _auto_bracket(l_codes, l_ts_ns, r_codes, r_ts_ns, r_seq_vals,
                  n_series, est_lanes, limit, valid_masks):
    """Exact host time-bracketing for oversize joins.

    Splits every series into (key, time-bracket) joint series — the
    same composition the explicit ``tsPartitionVal`` skew machinery
    uses — but instead of replicating a trailing *fraction* of each
    bracket (lossy beyond the lookback), it carries into each bracket
    the per-column last-non-null right row and the last right row
    overall from before the bracket's start.  Each joint series is then
    self-contained, so the bracketed join is **bit-identical** to the
    unbracketed one: the VERDICT "cannot execute at all" regime becomes
    slow-but-correct.

    ``valid_masks`` is ``[C, n_right]`` bool (per right column
    non-null), empty first axis for ``skipNulls=False`` where only the
    last-row channel is consumed.

    Returns ``(l_brackets, r_take, r_bracket_all, n_brackets, width_ns)``
    or ``None`` when the data cannot be split (zero time span)."""
    lo = min(int(l_ts_ns.min()), int(r_ts_ns.min()))
    hi = max(int(l_ts_ns.max()), int(r_ts_ns.max()))
    span = hi - lo + 1
    # enough brackets that a bracket's share of the dominant series sits
    # well under the limit (assuming rough uniformity in time; heavy
    # temporal skew degrades the bound, never correctness)
    n_brackets = int(-(-2 * est_lanes // max(limit, 1)))
    n_brackets = max(2, min(n_brackets, 1 << 16))
    width_ns = max(1, -(-span // n_brackets))
    if span <= 1:
        return None

    l_b = (l_ts_ns - lo) // width_ns
    r_b = (r_ts_ns - lo) // width_ns

    # right side in layout order (series-major, ts/seq-sorted) so
    # "last row before a boundary" is a searchsorted + prefix scan
    r_layout0 = packing.build_layout_from_codes(
        r_codes, r_ts_ns, r_seq_vals, n_series)
    rs_ts = r_layout0.ts_ns
    starts = r_layout0.starts
    n_r = len(r_codes)
    idx = np.arange(n_r, dtype=np.int64)
    last_valid = [
        np.maximum.accumulate(np.where(valid_masks[c][r_layout0.order],
                                       idx, -1))
        for c in range(valid_masks.shape[0])
    ] if n_r else []

    pairs = np.unique(
        np.stack([l_codes, l_b], axis=1), axis=0) if len(l_codes) else \
        np.zeros((0, 2), np.int64)
    carry_rows: List[int] = []
    carry_brackets: List[int] = []
    for k, b in pairs:
        s0, s1 = int(starts[k]), int(starts[k + 1])
        if s1 <= s0:
            continue
        boundary = lo + int(b) * width_ns
        p = s0 + int(np.searchsorted(rs_ts[s0:s1], boundary, side="left"))
        if p <= s0:
            continue
        carry = {p - 1}
        for lv in last_valid:
            j = int(lv[p - 1])
            if j >= s0:
                carry.add(j)
        for j in carry:
            carry_rows.append(j)
            carry_brackets.append(int(b))

    carried = np.asarray(carry_rows, dtype=np.int64)
    r_take = np.concatenate(
        [np.arange(n_r, dtype=np.int64), r_layout0.order[carried]])
    r_bracket_all = np.concatenate(
        [r_b, np.asarray(carry_brackets, dtype=np.int64)])
    return l_b, r_take, r_bracket_all, n_brackets, width_ns


def _prefixed(cols: List[str], prefix: Optional[str]) -> dict:
    if prefix is None or prefix == "":
        return {c: c for c in cols}
    return {c: f"{prefix}_{c}" for c in cols}


def _rows_copied(indexer: np.ndarray, n: int) -> int:
    """Rows ``iloc[indexer]`` copies out of an ``n``-row frame: none for
    the identity order, which pandas answers with a lazy copy (the test
    is the one ``DataFrame.take`` makes)."""
    return 0 if is_range_indexer(indexer, n) else len(indexer)


def _gather(values: np.ndarray, idx: np.ndarray, ok: np.ndarray):
    """Host gather with Spark-null semantics for any dtype."""
    if values.shape[0] == 0:
        # no right rows at all: every output is null
        ok = np.zeros(idx.shape, dtype=bool)
        values = np.empty(1, dtype=values.dtype)
    safe = np.where(ok, idx, 0)
    taken = values[safe]
    if values.dtype == object:
        out = taken.astype(object)
        out[~ok] = None
        return out
    if np.issubdtype(values.dtype, np.datetime64):
        out = taken.astype("datetime64[ns]")
        out[~ok] = np.datetime64("NaT")
        return out
    if np.issubdtype(values.dtype, np.floating):
        out = taken.astype(values.dtype)
        out[~ok] = np.nan
        return out
    if np.issubdtype(values.dtype, np.bool_):
        if ok.all():
            return taken
        out = pd.array(taken, dtype="boolean")
        out[~ok] = pd.NA
        return out
    # integers: keep exact dtype when fully matched, else nullable Int64
    if ok.all():
        return taken
    out = pd.array(taken.astype(np.int64), dtype="Int64")
    out[~ok] = pd.NA
    return out


def _binpack_worthwhile(l_layout, r_layout) -> bool:
    """Engage the bin-packed layout when one-series-per-row padding
    would waste most of the slot grid (Zipf-skewed key distributions).
    TEMPO_TPU_BINPACK=1/0 forces/forbids."""
    from tempo_tpu import config

    K = l_layout.n_series
    Ll = int(l_layout.lengths.max(initial=0))
    Lr = int(r_layout.lengths.max(initial=0))
    # the kernel's position payloads are exact in f32 up to 2^24 lanes:
    # a longer single series keeps the dense layout's exact int32
    # channels (this bound also caps SID_PAD collisions: series ids
    # stay far below 2^31)
    if max(Ll, Lr) >= (1 << 24) - 128:
        return False
    env = config.get("TEMPO_TPU_BINPACK")
    if env is not None:
        return env not in ("0", "false", "no")
    slots = K * (Ll + Lr)
    if slots == 0:
        return False
    return (l_layout.n_rows + r_layout.n_rows) / slots < 0.35


def _binpacked_indices(right, l_layout, r_layout, r_sorted_take,
                       valid_cols, max_lookback: int = 0,
                       r_seq_sorted=None, engine: str = "single",
                       interpret: bool = False):
    """Join indices through the bin-packed segmented kernel: short
    series share lane rows (packing.bin_pack_series), one program for
    any skew shape.  ``valid_cols`` empty = skipNulls=False (only the
    last-row channel is consumed).  ``max_lookback`` rides the
    sid-fenced windowed ladder (sortmerge._asof_merge_explicit) or the
    chunked streaming kernel.  ``r_seq_sorted`` (layout-ordered right
    sequence values) engages the tie-break — the layouts were sorted
    (ts, seq) per series so the segmented merge precondition holds
    (round-6 lift of the seq x bin-pack exclusion).  ``engine``:
    'chunked' runs the lane-chunked streaming VMEM kernel (oversize
    lane-row widths past the single-plan merge).

    Returns ``(take, planes, bp)``: ``planes`` the host index planes of
    the channels the join reads (one per column, or the last-row channel
    alone), holding right positions within the lane row; ``take`` every
    left row's flat position in them."""
    import jax.numpy as jnp

    from tempo_tpu.ops import pallas_merge as pm
    from tempo_tpu.ops import sortmerge as sm

    with span("tempo.pack", rows=l_layout.n_rows + r_layout.n_rows):
        Wl = packing.pad_length(
            max(int(l_layout.lengths.max(initial=0)), 1), 128)
        Wr = packing.pad_length(
            max(int(r_layout.lengths.max(initial=0)), 1), 128)
        bp = packing.bin_pack_series(
            l_layout.lengths, r_layout.lengths, Wl, Wr)
        K2 = packing.pad_length(bp.n_rows)
        # destination slots computed once, reused for every plane
        dest_l = packing.binpack_dest(l_layout.starts, bp.row, bp.l_off, Wl)
        dest_r = packing.binpack_dest(r_layout.starts, bp.row, bp.r_off, Wr)
        lt = packing.binpack_scatter(
            l_layout.ts_ns, dest_l, K2, Wl, packing.TS_PAD)
        rt = packing.binpack_scatter(
            r_layout.ts_ns, dest_r, K2, Wr, packing.TS_PAD)
        lsid = packing.binpack_scatter(
            l_layout.key_ids.astype(np.int32), dest_l, K2, Wl,
            packing.SID_PAD)
        rsid = packing.binpack_scatter(
            r_layout.key_ids.astype(np.int32), dest_r, K2, Wr,
            packing.SID_PAD)
        rv = np.stack([
            packing.binpack_scatter(
                (~pd.isna(right.df[c])).to_numpy()[r_sorted_take],
                dest_r, K2, Wr, False)
            for c in valid_cols
        ]) if valid_cols else np.zeros((0, K2, Wr), bool)
        rsq = (packing.binpack_scatter(r_seq_sorted, dest_r, K2, Wr, np.inf)
               if r_seq_sorted is not None else None)

    if engine == "chunked":
        take, planes = pm.asof_merge_indices_chunked(
            lt, rt, rv, dest_l, lsid, rsid, r_seq=rsq,
            skip_nulls=bool(valid_cols), max_lookback=int(max_lookback),
            interpret=interpret)
        return take, planes, bp
    last_idx, per_col = sm.asof_indices_binpacked(
        jnp.asarray(lt), jnp.asarray(rt), jnp.asarray(rv),
        jnp.asarray(lsid), jnp.asarray(rsid),
        max_lookback=int(max_lookback),
        r_seq=jnp.asarray(rsq) if rsq is not None else None)
    planes = (list(np.asarray(per_col)) if valid_cols
              else [np.asarray(last_idx)])
    return dest_l, planes, bp


def _right_starts(l_layout, r_layout, bp) -> np.ndarray:
    """Per left row (layout order), the flat right row its channel
    positions count from: the series' right start, less the series'
    lane offset when bin-packed (``bp``), as positions are then within
    the shared lane row."""
    start = r_layout.starts[:-1] - (bp.r_off if bp is not None else 0)
    return np.repeat(start, l_layout.lengths)


def _right_rows(plane: np.ndarray, take: np.ndarray, r_start: np.ndarray):
    """``(flat right row, ok)`` of every left row from one index channel:
    one take straight into left-row order, -1 (index planes) or NaN
    (the chunked kernel's f32 planes) marking no match."""
    ridx = np.take(plane.reshape(-1), take)
    ok = ridx >= 0
    flat = np.fmax(ridx, 0).astype(np.int64)   # fmax maps NaN to 0 too
    flat += r_start
    return flat, ok


def _joint_bracket_codes(l_codes, r_codes_taken, l_brackets, r_brackets):
    """Compose (key, time-bracket) joint series ids — shared by the
    explicit ``tsPartitionVal`` skew path and the oversize auto-bracket
    fallback so the encoding can never diverge between them.

    Returns ``(l_codes_j, r_codes_j, n_series)``."""
    all_codes = np.concatenate([l_codes, r_codes_taken])
    all_brackets = np.concatenate([l_brackets, r_brackets])
    joint = all_codes * np.int64(2 ** 31) + pd.factorize(all_brackets)[0]
    joint_codes, _ = pd.factorize(joint)
    n_series = int(joint_codes.max()) + 1
    nl = len(l_brackets)
    return (joint_codes[:nl].astype(np.int64),
            joint_codes[nl:].astype(np.int64), n_series)


def _time_brackets(ts_ns: np.ndarray, ts_partition_val: float):
    """Bracket id + remainder fraction, double-seconds math mirroring
    tsdf.py:176-180 (cast to double, truncate toward zero)."""
    ts_sec = ts_ns / packing.NS_PER_S
    bracket = ts_partition_val * (ts_sec / ts_partition_val).astype(np.int64)
    remainder = (ts_sec - bracket) / ts_partition_val
    return bracket, remainder


def asof_join(
    left,
    right,
    left_prefix: Optional[str] = None,
    right_prefix: str = "right",
    tsPartitionVal: Optional[float] = None,
    fraction: float = 0.5,
    skipNulls: bool = True,
    sql_join_opt: bool = False,
    suppress_null_warning: bool = False,
    maxLookback: int = 0,
):
    from tempo_tpu.frame import TSDF

    strategy = profiling.pick_asof_strategy(
        left.df, right.df, sql_join_opt,
        has_sequence=bool(right.sequence_col),
        max_lookback=int(maxLookback or 0),
    )
    broadcast_path = strategy == "broadcast"

    if tsPartitionVal is not None:
        if not skipNulls:
            raise ValueError(
                "Disabling null skipping with a partition value is not supported yet."
            )
        logger.warning(
            "You are using the skew version of the AS OF join. This may result in "
            "null values if there are any values outside of the maximum lookback. "
            "For maximum efficiency, choose smaller values of maximum lookback, "
            "trading off performance and potential blank AS OF values for sparse keys"
        )

    left._check_partition_cols_match(right)
    left._validate_ts_col_match(right)

    pcols = left.partitionCols

    left_value_cols = [c for c in left.df.columns if c not in pcols]
    right_value_cols = [c for c in right.df.columns if c not in pcols]
    lmap = _prefixed(left_value_cols, left_prefix)
    rmap = _prefixed(right_value_cols, right_prefix)

    _valid_cache: dict = {}

    def _right_valid(c: str) -> np.ndarray:
        """Right column non-null mask in original row order, computed
        once per column (shared by the oversize-bracket carries and the
        packed validity planes)."""
        if c not in _valid_cache:
            with span("tempo.pack", rows=len(right.df)):
                _valid_cache[c] = (~pd.isna(right.df[c])).to_numpy()
        return _valid_cache[c]

    # --- joint key encoding over the union of both sides' keys ---------
    l_codes, r_codes, key_frame = packing.encode_keys_joint(left.df, right.df, pcols)
    l_ts_ns = packing.series_to_ns(left.df[left.ts_col])
    r_ts_ns = packing.series_to_ns(right.df[right.ts_col])

    r_seq_vals = (
        pd.to_numeric(right.df[right.sequence_col]).to_numpy(dtype=np.float64)
        if right.sequence_col
        else None
    )
    if r_seq_vals is not None:
        # Spark orders the merged stream by (ts, seq ASC NULLS FIRST,
        # rec_ind) — tsdf.py:117-121: a null-seq right row sorts before
        # tied-ts left rows (visible to them) and loses the tie to
        # non-null-seq right rows.  -inf realises NULLS FIRST in the
        # float total order both for the layout sort and the merge key.
        r_seq_vals = np.where(np.isnan(r_seq_vals), -np.inf, r_seq_vals)

    # --- skew variant: compose key with overlapping time brackets ------
    r_take = np.arange(len(right.df), dtype=np.int64)
    if broadcast_path:
        # the reference's sql_join_opt fast path returns before any skew
        # handling (tsdf.py:492-509) — the broadcast join never buckets
        tsPartitionVal = None
    if tsPartitionVal is not None:
        l_bracket, _ = _time_brackets(l_ts_ns, tsPartitionVal)
        r_bracket, r_rem = _time_brackets(r_ts_ns, tsPartitionVal)
        # replicate the trailing `fraction` of each right bracket forward
        spill = r_rem >= (1.0 - fraction)
        r_take = np.concatenate([r_take, r_take[spill]])
        r_bracket = np.concatenate(
            [r_bracket, r_bracket[spill] + tsPartitionVal]
        )
        # re-encode keys as (key, bracket)
        l_codes_j, r_codes_j, n_series = _joint_bracket_codes(
            l_codes, r_codes[r_take], l_bracket, r_bracket)
        r_ts_j = r_ts_ns[r_take]
        r_seq_j = r_seq_vals[r_take] if r_seq_vals is not None else None
    else:
        n_series = len(key_frame)
        l_codes_j, r_codes_j = l_codes, r_codes
        r_ts_j = r_ts_ns
        r_seq_j = r_seq_vals

    # --- oversize engine pick: single-plan -> chunked -> brackets -----
    # Past the merge-plan limit one device program cannot run: the XLA
    # sort ladder OOM-kills the compiler at ~205K merged lanes (VERDICT
    # missing #1).  Since round 6 the default oversize engine is the
    # lane-chunked streaming VMEM merge (ops/pallas_merge.py) — on-chip
    # at any length under 2^24 merged rows, every flag combination
    # including maxLookback.  Host time-bracketing remains the last
    # resort (non-TPU backends, >= 2^24 rows), selectable explicitly
    # with TEMPO_TPU_JOIN_ENGINE=bracket.
    auto_bracketed = False
    join_engine = "single"
    if tsPartitionVal is None and not broadcast_path \
            and len(left.df) and len(right.df):
        from tempo_tpu.ops import pallas_merge as pm

        limit = resilience.max_merged_lanes()
        with span("tempo.pack", rows=len(l_codes) + len(r_codes)):
            est = _estimate_merged_lanes(l_codes, r_codes, n_series)
        # the availability probe scans the seq column (seq_kernel_form)
        # — only pay it when the engine decision actually needs it
        # (oversize, or an explicit TEMPO_TPU_JOIN_ENGINE override)
        if 0 < limit < est or profiling.join_engine_override():
            chunked_ok = pm.chunked_join_available(
                est, len(right_value_cols), r_seq_vals,
                skip_nulls=skipNulls, max_lookback=int(maxLookback or 0))
            join_engine = profiling.pick_join_engine(est, limit,
                                                    chunked_ok)
        if join_engine == "chunked" and 0 < limit < est:
            logger.info(
                "asofJoin: estimated %d merged lanes exceeds the "
                "single-program limit %d; using the lane-chunked "
                "streaming merge engine", est, limit,
            )
        if join_engine == "bracket":
            if maxLookback and int(maxLookback) > 0:
                logger.warning(
                    "asofJoin: bracket engine selected (estimated %d "
                    "merged lanes, limit %d), but maxLookback counts "
                    "rows of the full merged stream and cannot ride "
                    "the bracketing fallback — attempting the "
                    "full-size merge (may exhaust compiler memory)",
                    est, limit,
                )
                join_engine = "single"
            else:
                carry_cols = right_value_cols if skipNulls else []
                masks = np.stack([
                    _right_valid(c) for c in carry_cols
                ]) if carry_cols else np.zeros((0, len(right.df)), bool)
                plan = _auto_bracket(
                    l_codes, l_ts_ns, r_codes, r_ts_ns, r_seq_vals,
                    n_series, est, limit, masks,
                )
                if plan is not None:
                    l_b, r_take, r_bracket_all, n_brackets, width_ns = plan
                    l_codes_j, r_codes_j, n_series = _joint_bracket_codes(
                        l_codes, r_codes[r_take], l_b, r_bracket_all)
                    r_ts_j = r_ts_ns[r_take]
                    r_seq_j = (r_seq_vals[r_take]
                               if r_seq_vals is not None else None)
                    auto_bracketed = True
                    logger.warning(
                        "asofJoin: estimated %d merged lanes vs the "
                        "merge-plan limit %d; %s the host "
                        "time-bracketing path (%d brackets, width %.0fs, "
                        "%d carried rows). Results are exact but "
                        "execution is slower — deferred audit: oversize "
                        "AS-OF join rerouted instead of compiler OOM.",
                        est, limit,
                        ("degrading to" if est > limit
                         else "TEMPO_TPU_JOIN_ENGINE forced"),
                        n_brackets,
                        width_ns / packing.NS_PER_S,
                        len(r_take) - len(right.df),
                    )

    l_layout = packing.build_layout_from_codes(l_codes_j, l_ts_ns, None, n_series)
    r_layout = packing.build_layout_from_codes(r_codes_j, r_ts_j, r_seq_j, n_series)

    r_sorted_take = r_take[r_layout.order]

    # --- layout strategy: bin-pack Zipf-skewed key distributions ------
    # One-series-per-row padding pays for the LONGEST series at every
    # key (a real NBBO day is ~96% padding); when slot occupancy is low
    # the series bin-pack into shared lane rows and the segmented merge
    # kernel joins them independently (the packed-layout answer to the
    # reference's tsPartitionVal skew machinery, tsdf.py:164-190 —
    # which remains available explicitly).  Skew brackets and the
    # broadcast path keep the dense layout; a sequence tie-break rides
    # the bin-packed layout too since round 6 (the layouts sort
    # (ts, seq) per series when a seq plane is present, so the
    # segmented merge precondition holds); maxLookback rides the
    # sid-fenced windowed ladder (round 4) or the chunked streaming
    # kernel (round 6).
    import jax as _jax

    interp_chunked = _jax.default_backend() != "tpu"
    use_binpack = (
        not broadcast_path
        and tsPartitionVal is None
        and n_series > 1
        and _binpack_worthwhile(l_layout, r_layout)
    )
    # the join reads the per-column channels under skipNulls, else the
    # last-right-row channel alone; each engine hands them back as host
    # planes with ``take``, every left row's flat position in them
    read_cols = skipNulls and not broadcast_path
    keep_mask_packed = None
    if use_binpack:
        with span("tempo.dispatch") as fetched:
            take, planes, bp = _binpacked_indices(
                right, l_layout, r_layout, r_sorted_take,
                right_value_cols if skipNulls else [],
                max_lookback=int(maxLookback or 0),
                r_seq_sorted=(r_seq_j[r_layout.order]
                              if r_seq_j is not None else None),
                engine=join_engine, interpret=interp_chunked,
            )
            fetched.rows = sum(p.size for p in planes)
    else:
        bp = None

    Ll = packing.pad_length(int(l_layout.lengths.max(initial=0)))
    Lr = packing.pad_length(int(r_layout.lengths.max(initial=0)))
    if not use_binpack:
        with span("tempo.unpack"):
            # each left row's flat lane in the packed [K, Ll] left side
            take = packing.binpack_dest(
                l_layout.starts, np.arange(n_series),
                np.zeros(n_series, np.int64), Ll)
        with span("tempo.pack", rows=l_layout.n_rows + r_layout.n_rows):
            l_ts_p = packing.pack_column(
                l_layout.ts_ns, l_layout, Ll, fill=packing.TS_PAD)
            r_ts_p = packing.pack_column(
                r_layout.ts_ns, r_layout, Lr, fill=packing.TS_PAD)

            # validity masks per right column (order: right_value_cols)
            r_valid_packed = []
            for c in right_value_cols:
                valid = _right_valid(c)[r_sorted_take]
                r_valid_packed.append(
                    packing.pack_column(valid, r_layout, Lr, fill=False)
                )
            r_valids = np.stack(r_valid_packed) if r_valid_packed else \
                np.zeros((0, n_series, Lr), bool)
            r_seq_packed = (
                packing.pack_column(
                    r_seq_j[r_layout.order], r_layout, Lr, fill=np.inf
                )
                if r_seq_j is not None and not broadcast_path
                else None
            )

    # --- kernel dispatch ----------------------------------------------
    # (from the first device call to the last blocking fetch)
    use_merge = strategy == "merge"
    if not use_binpack:
        with span("tempo.dispatch") as fetched:
            if broadcast_path:
                last_row_idx, matched = asof_ops.asof_indices_inner(
                    l_ts_p, r_ts_p)
                planes = [np.asarray(last_row_idx)]  # nulls included
                keep_mask_packed = np.asarray(matched)
            elif join_engine == "chunked":
                from tempo_tpu.ops import pallas_merge as pm

                take, planes = pm.asof_merge_indices_chunked(
                    l_ts_p, r_ts_p, r_valids, take, r_seq=r_seq_packed,
                    skip_nulls=skipNulls,
                    max_lookback=int(maxLookback or 0),
                    interpret=interp_chunked,
                )
            else:
                if use_merge:
                    last_row_idx, per_col_idx = asof_ops.asof_indices_merge(
                        l_ts_p, None, r_ts_p, r_seq_packed, r_valids,
                        n_cols=len(right_value_cols),
                        max_lookback=int(maxLookback),
                    )
                else:
                    last_row_idx, per_col_idx = \
                        asof_ops.asof_indices_searchsorted(
                            l_ts_p, r_ts_p, r_valids,
                            n_cols=len(right_value_cols))
                planes = (list(np.asarray(per_col_idx)) if read_cols
                          else [np.asarray(last_row_idx)])
            fetched.rows = sum(a.size for a in (*planes, keep_mask_packed)
                               if a is not None)

    # --- flatten back to left row coordinates --------------------------
    n_left = l_layout.n_rows
    with span("tempo.unpack"):
        r_start = _right_starts(l_layout, r_layout, bp)
    taken = []
    for plane in planes:
        with span("tempo.unpack", rows=n_left):
            taken.append(_right_rows(plane, take, r_start))

    # The left columns go into the output as Series: each keeps its
    # dtype and storage (an Arrow string column stays Arrow, no Python
    # objects), and copy-on-write keeps the result and the caller's
    # frame apart.  A side already in layout order is only wrapped.
    out = {}
    with span("tempo.frame", rows=_rows_copied(l_layout.order, n_left)
              + _rows_copied(r_sorted_take, len(right.df))):
        left_sorted = left.df.iloc[l_layout.order].reset_index(drop=True)
        for c in pcols:
            out[c] = left_sorted[c]
        for c in left_value_cols:
            out[lmap[c]] = left_sorted[c]
        r_sorted_df = right.df.iloc[r_sorted_take].reset_index(drop=True)

    with span("tempo.unpack"):
        for ci, c in enumerate(right_value_cols):
            flat, ok = taken[ci] if read_cols else taken[0]
            vals = r_sorted_df[c].to_numpy()
            col_out = _gather(vals, flat, ok)
            if (not skipNulls) and not broadcast_path:
                # last right row's value, nulls included (tsdf.py:123-136)
                col_valid = (~pd.isna(r_sorted_df[c])).to_numpy()
                ok2 = ok & col_valid[np.where(ok, flat, 0)]
                col_out = _gather(vals, flat, ok2)
            out[rmap[c]] = col_out
            if (
                tsPartitionVal is not None
                and not suppress_null_warning
                and logger.isEnabledFor(logging.WARNING)
            ):
                if (~ok).any():
                    logger.warning(
                        "Column " + rmap[c] + " had no values within the "
                        "lookback window. Consider using a larger window to "
                        "avoid missing values. If this is the first record "
                        "in the data frame, this warning can be ignored."
                    )

    with span("tempo.frame") as framed:
        # the right columns are the fresh arrays _gather made: wrapping
        # them, like the left Series, copies nothing
        res = pd.DataFrame(out, copy=False)
        if broadcast_path:
            # the inner-join filter, taken into left-row order like the
            # index channels
            keep = np.take(keep_mask_packed.reshape(-1), take)
            res = res[keep].reset_index(drop=True)
            if len(res) < n_left:
                framed.rows += len(res)
        if tsPartitionVal is not None or auto_bracketed:
            # the joint (key, bracket) layout emits rows in bracket order;
            # restore the same (key, ts) order the non-skew path produces
            # so the two strategies are interchangeable row-for-row
            perm = np.lexsort(
                (l_ts_ns[l_layout.order], l_codes[l_layout.order])
            )
            framed.rows += _rows_copied(perm, len(res))
            res = res.iloc[perm].reset_index(drop=True)

        new_ts = lmap[left.ts_col]
        joined = TSDF(res, ts_col=new_ts, partition_cols=pcols)
        # free the sorted copies and the join's planes (GBs at full
        # size) inside the phase, not after it in the op's own time
        del left_sorted, r_sorted_df, out, res, taken, planes, take
        return joined
