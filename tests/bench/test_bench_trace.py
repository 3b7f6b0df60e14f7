"""Trace → device metrics: union busy time, idle gaps labelled by the
benchmark's host spans, and device time per operation name."""

import pathlib

import pytest

from bench import trace as T

DATA = pathlib.Path(__file__).parent / "data"
PLANE = "/device:TPU:0"


def synthetic():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 10.0),   # overlap
           ("sample_attr_kernel", 30.0, 20.0),
           ("fusion.1", 60.0, 5.0), ("copy", 95.0, 10.0)]      # past window
    spans = [("bench/window", 0.0, 100.0), ("bench/call", 0.0, 55.0),
             ("bench/drain", 20.0, 25.0), ("bench/call", 58.0, 90.0)]
    return T.Trace(device_ops={PLANE: ops}, spans=spans)


def test_merge_and_busy_union():
    m = T.merge([(5, 15), (0, 10), (30, 50), (50, 52)])
    assert m.tolist() == [[0, 15], [30, 52]]
    tr = synthetic()
    assert T.busy_ns(tr, PLANE, 0.0, 100.0) == 15 + 20 + 5 + 5


def test_idle_gaps_and_their_labels():
    tr = synthetic()
    gaps = T.idle_gaps(tr, PLANE, 0.0, 100.0)
    assert gaps == [(15.0, 30.0), (50.0, 60.0), (65.0, 95.0)]
    labels = T.label_gaps(gaps, tr.spans)
    # (15, 30) midpoint 22.5 lies in the drain inside a call; (50, 60)
    # midpoint 55 lies between the calls; (65, 95) midpoint 80 in a call
    assert labels == {"drain": 15.0, "host:other": 10.0, "call": 30.0}


def test_summary_idle_share_and_breakdown():
    s = T.summarize(synthetic())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)
    assert s.op_ns == {"fusion.1": 15.0, "fusion.2": 10.0,
                       "sample_attr_kernel": 20.0, "copy": 5.0}
    # launches that start inside the window
    assert s.op_count == {"fusion.1": 2, "fusion.2": 1,
                          "sample_attr_kernel": 1, "copy": 1}
    b = s.breakdown()
    assert b["device_ops"][0] == ["sample_attr_kernel", pytest.approx(20e-9)]
    assert b["idle_gaps"][0] == ["call", pytest.approx(30e-9)]


def test_window_span_is_required():
    with pytest.raises(ValueError):
        T.Trace(device_ops={PLANE: []}, spans=[]).window()


def recorded():
    import json
    d = json.loads((DATA / "region_call_trace.json").read_text())
    return d, T.Trace(device_ops=d["device_ops"],
                      spans=[tuple(s) for s in d["spans"]])


def test_op_labels_of_recorded_hlo_names():
    d, _ = recorded()
    labels = [T.op_label(n) for n in d["raw_op_names"]]
    assert "body.63 f32[8,1024] tpu_custom_call" in labels
    assert "fusion.573 (u32[1],u32[1])" in labels
    assert all(" = " not in lab and "{" not in lab for lab in labels)
    assert T.is_control_flow("while.60 (s32[],u32[1024])")


def test_recorded_chip_trace_reduces():
    """Two fused attribution calls traced on a v5e: the device is busy
    inside the calls only, and the reduction's launches of the Pallas
    kernel (4 channels a chunk, one chunk a call) are found by their
    signature and count."""
    from bench.layer_metrics import sample_attr_roofline as K
    _, tr = recorded()
    s = T.summarize(tr)
    assert s.window_s == pytest.approx(0.029366338)
    assert 0.0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(0.01327829)
    assert set(s.idle_by_span_ns) == {"call"}
    assert sum(s.idle_by_span_ns.values()) * 1e-9 == pytest.approx(
        s.window_s - s.busy_s)
    assert not any(T.is_control_flow(k) for k in s.op_ns)
    assert K.kernel_ns(s, regions=1024, launches=8) == pytest.approx(
        8 * 268_600, rel=0.01)


@pytest.mark.parametrize("regions,launches", [(1024, 4), (1024, 12),
                                              (2048, 8), (100, 8)])
def test_kernel_reader_refuses_launches_it_cannot_account_for(
        regions, launches):
    """Another launch count, or a statistics block of another width, is
    an error and not a silent metric."""
    from bench.layer_metrics import sample_attr_roofline as K
    s = T.summarize(recorded()[1])
    with pytest.raises(K.KernelNotFound):
        K.kernel_ns(s, regions=regions, launches=launches)


def test_kernel_reader_is_silent_where_no_pallas_kernel_runs():
    from bench.layer_metrics import sample_attr_roofline as K
    s = T.summarize(synthetic())
    assert K.kernel_ns(s, regions=1024, launches=8) is None


def test_recorder_lists_each_steps_operations_in_time_order():
    """``bench/record_program.py``'s reduction, on the recorded chip trace
    with its two calls as the steps: every operation that is not control
    flow, timed from its step's first operation."""
    from bench import manifest
    rec = manifest.load_module(manifest.BENCH_DIR / "record_program.py")
    _, tr = recorded()
    rows = rec.step_rows(tr, "bench/call")
    for k in (0, 1):
        mine = [r for r in rows if r[0] == k]
        assert len(mine) == 248
        starts = [r[2] for r in mine]
        assert starts[0] == 0 and starts == sorted(starts)
        assert all(r[3] >= 0 for r in mine)
    assert not any(T.is_control_flow(r[1]) for r in rows)
    empty = T.Trace(device_ops={PLANE: []}, spans=[("bench/step", 0, 1)])
    with pytest.raises(ValueError):
        rec.step_rows(empty, "bench/step")
