"""Storage-plane chaos: the kill/corrupt campaign at smoke sizes, the
legacy-writer overwrite data-loss fix (never delete the old table
before its replacement exists), and the hardened ``io.writer.read``
path (corrupt row groups named, quarantined, never an opaque
traceback)."""

import os

import numpy as np
import pandas as pd
import pytest

from tempo_tpu.frame import TSDF
from tempo_tpu.io import writer
from tempo_tpu.io.ingest import CorruptRowGroupError
from tempo_tpu.testing import chaos, faults

pytestmark = pytest.mark.chaos


def mk_tsdf(n=400, seed=3, n_keys=4):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "symbol": rng.choice([f"s{k}" for k in range(n_keys)], n),
        "event_ts": pd.to_datetime(
            np.sort(rng.integers(0, 10 ** 6, n)) * 1_000_000_000),
        "px": rng.standard_normal(n),
    })
    return df, TSDF(df, ts_col="event_ts", partition_cols=["symbol"])


def read_px(name, base_dir):
    return (writer.read(name, base_dir=base_dir).df
            .sort_values(["symbol", "event_ts"], kind="stable")
            .px.to_numpy())


# ----------------------------------------------------------------------
# The legacy (delta-format) overwrite: staged sibling + atomic swap
# ----------------------------------------------------------------------

class TestDeltaOverwriteSurvivesKills:
    """Satellite proof of the data-loss fix: the seed-era write()
    rmtree'd the live table before writing its replacement — a kill in
    the window lost BOTH tables.  Now a kill at every point of the
    staged swap leaves the old table readable."""

    def _seed_table(self, tmp_path):
        df1, t1 = mk_tsdf(seed=1)
        writer.write(t1, "tab", base_dir=str(tmp_path), format="delta")
        old = read_px("tab", str(tmp_path))
        _, t2 = mk_tsdf(seed=2)
        return old, t2

    def test_kill_mid_build_keeps_old_table(self, tmp_path):
        old, t2 = self._seed_table(tmp_path)
        with pytest.raises(faults.SimulatedKill):
            with faults.FaultInjector().kill_on_call(
                    writer, "_write_delta", call_no=1):
                writer.write(t2, "tab", base_dir=str(tmp_path),
                             format="delta")
        np.testing.assert_array_equal(read_px("tab", str(tmp_path)),
                                      old)
        # no staging residue poisons the NEXT write
        writer.write(t2, "tab", base_dir=str(tmp_path), format="delta")

    def test_kill_mid_fsync_keeps_old_table(self, tmp_path):
        old, t2 = self._seed_table(tmp_path)
        with pytest.raises(faults.SimulatedKill):
            with faults.FaultInjector().kill_on_call(
                    writer, "_fsync_tree", call_no=1):
                writer.write(t2, "tab", base_dir=str(tmp_path),
                             format="delta")
        np.testing.assert_array_equal(read_px("tab", str(tmp_path)),
                                      old)

    def test_kill_between_swap_renames_reads_bak(self, tmp_path):
        # the worst window: old table already moved to .bak, staged
        # table not yet live — read() finds the .bak survivor
        old, t2 = self._seed_table(tmp_path)
        with pytest.raises(faults.SimulatedKill):
            with faults.FaultInjector().kill_on_call(
                    writer.os, "replace", call_no=2):
                writer.write(t2, "tab", base_dir=str(tmp_path),
                             format="delta")
        assert not os.path.isdir(os.path.join(str(tmp_path), "tab"))
        assert os.path.isdir(os.path.join(str(tmp_path), "tab.bak"))
        np.testing.assert_array_equal(read_px("tab", str(tmp_path)),
                                      old)
        # the re-issued write completes and clears the .bak
        writer.write(t2, "tab", base_dir=str(tmp_path), format="delta")
        assert not os.path.isdir(os.path.join(str(tmp_path), "tab.bak"))


# ----------------------------------------------------------------------
# writer.read through the hardened ingest path
# ----------------------------------------------------------------------

def _corrupt_one_committed_segment(tmp_path):
    from tempo_tpu.store import engine as se

    df, tsdf = mk_tsdf(n=600)
    writer.write(tsdf, "tab", base_dir=str(tmp_path))
    store = se.Store(str(tmp_path))
    gen_dir = store.dataset_path("tab")
    segs = sorted(p for p in os.listdir(gen_dir)
                  if p.endswith(".parquet"))
    # writer.write clusters with the default segment size -> force a
    # multi-segment table first if needed
    if len(segs) < 2:
        store.write_table("tab", store.read("tab"),
                          ["symbol", "event_time"],
                          source_fp="resegment", segment_rows=150)
        gen_dir = store.dataset_path("tab")
        segs = sorted(p for p in os.listdir(gen_dir)
                      if p.endswith(".parquet"))
    assert len(segs) >= 2
    rec = faults.corrupt_parquet_row_group(
        os.path.join(gen_dir, segs[0]))
    return df, rec


def test_read_names_corrupt_row_group(tmp_path):
    _, rec = _corrupt_one_committed_segment(tmp_path)
    with pytest.raises(CorruptRowGroupError) as ei:
        writer.read("tab", base_dir=str(tmp_path))
    msg = str(ei.value)
    assert os.path.basename(rec["file"]) in msg
    assert f"[rg {rec['row_group']}]" in msg
    assert ei.value.ranges          # exact ranges ride the exception


def test_read_quarantine_reads_around_corruption(tmp_path):
    df, rec = _corrupt_one_committed_segment(tmp_path)
    out = writer.read("tab", base_dir=str(tmp_path),
                      on_corrupt="quarantine")
    # every surviving row is bitwise one of the source rows, and
    # exactly the quarantined row-group's rows are missing
    assert len(out.df) == len(df) - rec["rows"]
    merged = out.df.merge(
        df.drop_duplicates(), on=["symbol", "event_ts", "px"],
        how="left", indicator=True)
    assert (merged["_merge"] == "both").all()


def test_store_errors_classify_for_retry_policy(tmp_path):
    from tempo_tpu import resilience
    from tempo_tpu.resilience import FailureKind
    from tempo_tpu.store import engine as se

    _, tsdf = mk_tsdf()
    writer.write(tsdf, "tab", base_dir=str(tmp_path))
    cpath = os.path.join(str(tmp_path), "tab", se.CURRENT_NAME)
    blob = open(cpath, "rb").read()
    open(cpath, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(se.StoreCommitError) as ei:
        writer.read("tab", base_dir=str(tmp_path))
    # a torn commit/pointer is NEVER transient: retrying re-reads the
    # same bad bytes
    assert resilience.classify(ei.value) is \
        FailureKind.CORRUPTED_ARTIFACT


# ----------------------------------------------------------------------
# The campaign smoke (the storage-plane campaign at tiny sizes)
# ----------------------------------------------------------------------

def test_store_campaign_smoke(tmp_path):
    rep = chaos.run_store_campaign(
        str(tmp_path), rows=4_000, n_keys=6, seed=31,
        segment_rows=600, n_streams=10, resident_budget=3,
        events_per_stream=6)
    wr = rep["write_resume"]
    assert wr["segments_rewritten_committed"] == 0
    assert wr["pointer_swing_resume_segment_writes"] == 0
    assert "bitwise" in wr["value_audit"]
    assert all(rep["refusals_by_name"].values())
    assert rep["legacy_overwrite"]["old_table_lost"] is False
    assert rep["compaction"]["killed_mid_merge"] is True
    assert "bitwise" in rep["compaction"]["reader_on_old_generation"]
    cs = rep["cohort_spill"]
    assert cs["spills"] >= 1 and cs["restores"] >= 1
    assert "bitwise" in cs["value_audit"]
    assert rep["no_silent_restores"] is True
