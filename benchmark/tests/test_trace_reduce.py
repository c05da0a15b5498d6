"""The trace reduction on a trace synthesized in the xplane format.

Device 0 runs four operations inside an 8 us window (two overlap, one
starts before the window, one ends after it); device 1 runs one.  The
host holds the window span and two harness spans."""

import pytest

import trace_reduce

US = 1_000_000  # picoseconds


def _events(md_ids_and_spans):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {s} duration_ps: {d} }}"
        for m, s, d in md_ids_and_spans)


def _md(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in names.items())


TRACE = f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 0, 2 * US), (2, 3 * US // 2, 3 * US // 2),
              (3, 6 * US, 1 * US), (1, 17 * US // 2, 3 * US // 2)])} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(4, US // 2, 27 * US // 10), (5, 6 * US, 1 * US)])} }}
  {_md({1: "fusion.1", 2: "fusion.2", 3: "custom-call.1",
        4: "jit_asof_merge_indices_pallas(7)", 5: "jit_ema(3)"})}
}}
planes {{
  id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 1 * US, 4 * US)])} }}
  {_md({1: "fusion.9"})}
}}
planes {{
  id: 3 name: "/host:CPU"
  lines {{ id: 7 name: "main" timestamp_ns: 0
    {_events([(1, 1 * US, 8 * US), (2, 5 * US // 2, 4 * US),
              (3, 15 * US // 2, US // 2), (4, 0, 1 * US)])} }}
  {_md({1: "bench.window", 2: "bench.asofJoin", 3: "bench.df",
        4: "PjRtExecute"})}
}}
'''


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(TRACE)
    return trace_reduce.reduce_profile(ProfileData.from_serialized_xspace(raw))


def test_window_and_idle_share(summary):
    # window 1-9 us; device 0 busy [1, 3] + [6, 7] + [8.5, 9] = 3.5 us,
    # device 1 busy [1, 5] = 4 us
    assert summary.devices == 2
    assert summary.window_s == pytest.approx(8e-6)
    assert summary.busy_s == pytest.approx((3.5e-6 + 4e-6) / 2)
    assert summary.idle_share() == pytest.approx(1 - 3.75 / 8)


def test_device_time_per_operation_and_program(summary):
    assert summary.op_s["fusion.1"] == pytest.approx(1.5e-6)
    assert summary.op_s["fusion.2"] == pytest.approx(1.5e-6)
    assert summary.op_s["custom-call.1"] == pytest.approx(1e-6)
    assert summary.op_s["fusion.9"] == pytest.approx(4e-6)
    assert summary.time_matching(("asof_merge",)) == pytest.approx(2.2e-6)
    assert summary.time_matching(("ema", "asof")) == pytest.approx(3.2e-6)


def test_idle_gaps_go_to_the_innermost_host_span(summary):
    assert summary.gaps == [(3000.0, 6000.0), (7000.0, 8500.0)]
    assert summary.gap_s_by_span == pytest.approx(
        {"bench.asofJoin": 3e-6, "bench.df": 1.5e-6})
    top = trace_reduce.breakdown(summary)
    assert top["idle_gaps"][0][0] == "bench.asofJoin"
    assert top["device_ops"][0] == ["fusion.9", pytest.approx(4e-6)]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 6)]


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(ProfileData.from_serialized_xspace(raw))
