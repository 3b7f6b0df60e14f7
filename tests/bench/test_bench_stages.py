"""Device time by stage of the fused call: op names → innermost
``alea/<stage>``, device ns per stage in a window, ns per sample, and
the coverage that a lost scope would break."""

import json
import pathlib

import pytest

from bench import stages as S
from bench import trace as T

DATA = pathlib.Path(__file__).parent / "data"
PLANE = "/device:TPU:0"


@pytest.mark.parametrize("op_name,stage", [
    ("jit(run)/while/body/alea/clock/jit(_uniform)/add", "clock"),
    ("jit(run)/while/body/vmap(alea/lookup)/jit(searchsorted)/gather",
     "lookup"),
    # scopes nest: the innermost names the operation
    ("jit(run)/while/body/alea/sensor/alea/sensor/vmap(alea/lookup)/gather",
     "lookup"),
    ("jit(run)/while/body/alea/sensor/vmap()/gather", "sensor"),
    ("jit(run)/while/body/alea/reduce/sample_attr/pallas_call", "reduce"),
    ("jit(run)/while/body/add", "unscoped"),
    ("", "unscoped"),
    ("jit(run)/alea/lookups/gather", "unscoped"),       # not a stage
    ("jit(run)/xalea/clock/add", "unscoped"),
])
def test_innermost_stage_of_an_op_name(op_name, stage):
    assert S.stage_of(op_name) == stage


def synthetic():
    """A window of 100 ns: four stages, a while loop that spans its body,
    an unscoped copy, and an op that runs past the window's end."""
    ops = [("while.1 (s32[])", 0.0, 90.0),
           ("fusion.1 f32[8]", 0.0, 10.0),          # clock
           ("fusion.2 f32[8]", 10.0, 40.0),         # lookup
           ("fusion.3 f32[8,3]", 50.0, 20.0),       # sensor
           ("sample_attr.1 f32[8,8] tpu_custom_call", 70.0, 15.0),
           ("copy.1 f32[8]", 85.0, 5.0),            # unscoped
           ("fusion.2 f32[8]", 95.0, 10.0)]         # 5 ns inside
    names = {"while.1 (s32[])": "jit(run)/while",
             "fusion.1 f32[8]": "jit(run)/while/body/alea/clock/add",
             "fusion.2 f32[8]": "jit(run)/while/body/alea/sensor/"
                                "vmap(alea/lookup)/gather",
             "fusion.3 f32[8,3]": "jit(run)/while/body/alea/sensor/mul",
             "sample_attr.1 f32[8,8] tpu_custom_call":
                 "jit(run)/while/body/alea/reduce/sample_attr/pallas_call",
             "copy.1 f32[8]": "jit(run)/copy"}
    tr = T.Trace(device_ops={PLANE: ops}, spans=[("bench/window", 0.0, 100.0)])
    return T.summarize(tr), names


def test_stage_ns_of_a_synthetic_window():
    s, names = synthetic()
    by = S.stage_ns(s.op_ns, names)
    assert by == {"clock": 10.0, "lookup": 45.0, "sensor": 20.0,
                  "reduce": 15.0, "unscoped": 5.0}
    assert S.coverage(by) == pytest.approx(90 / 95)
    assert S.ns_per_sample(by, 5, "lookup") == pytest.approx(9.0)
    assert S.ns_per_sample(by, 5, "clock") == pytest.approx(2.0)


def test_stages_covering_too_little_are_an_error():
    s, names = synthetic()
    names["fusion.3 f32[8,3]"] = "jit(run)/while/body/mul"   # scope lost
    by = S.stage_ns(s.op_ns, names)
    assert by["unscoped"] == 25.0 and S.coverage(by) < S.COVERAGE
    with pytest.raises(S.StageCoverage):
        S.ns_per_sample(by, 5, "lookup")


def test_no_stage_reads_nothing():
    s, names = synthetic()
    unscoped = {k: "jit(step)/add" for k in names}
    assert S.stage_ns(s.op_ns, unscoped) is None
    assert S.stage_ns(s.op_ns, {}) is None
    assert S.ns_per_sample(None, 5, "lookup") is None


def test_a_program_recorded_without_scopes_reads_nothing():
    """The chip trace recorded before the program named its stages."""
    d = json.loads((DATA / "region_call_trace.json").read_text())
    tr = T.Trace(device_ops=d["device_ops"],
                 spans=[tuple(x) for x in d["spans"]])
    assert S.stage_ns(T.summarize(tr).op_ns, {}) is None


def recorded():
    d = json.loads((DATA / "region_stages_trace.json").read_text())
    return d, T.Trace(device_ops=d["device_ops"],
                      spans=[tuple(x) for x in d["spans"]])


def test_recorded_call_by_stage():
    """One fused call of the cell traced on a v5e with the scopes: the
    binary searches take most of it, every stage but the clock (fused
    into its consumers) reads time, and the stages with the unscoped
    rest account for the device's busy time."""
    d, tr = recorded()
    s = T.summarize(tr)
    by = S.stage_ns(s.op_ns, d["op_names"])
    assert S.coverage(by) >= S.COVERAGE
    per = {k: S.ns_per_sample(by, d["samples_per_call"], k)
           for k in S.STAGES}
    assert per["lookup"] == pytest.approx(664.3, rel=0.01)
    assert per["sensor"] == pytest.approx(72.56, rel=0.01)
    assert per["reduce"] == pytest.approx(4.904, rel=0.01)
    assert 0 < per["clock"] < 0.1
    assert sum(by.values()) == pytest.approx(s.busy_s * 1e9, rel=0.01)
    top = sorted(s.op_ns, key=s.op_ns.get, reverse=True)[:4]
    assert {S.stage_of(d["op_names"][k]) for k in top} == {"lookup"}


def test_named_kernel_is_found_by_the_signature_reader():
    """The kernel now runs as ``sample_attr.<n>``; the roofline reader,
    which matches its result and launch count, finds every launch:
    4 channels a chunk, 4 chunks a call."""
    from bench.layer_metrics import sample_attr_roofline as K
    d, tr = recorded()
    s = T.summarize(tr)
    mine = [k for k in s.op_count if k.startswith("sample_attr.")]
    assert mine and all(S.stage_of(d["op_names"][k]) == "reduce"
                        for k in mine)
    launches = d["chunks"] * 4
    assert K.kernel_ns(s, regions=d["regions"], launches=launches) == \
        pytest.approx(sum(s.op_ns[k] for k in mine))


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 6000 duration_ps: 1000
             stats { metadata_id: 7 str_value: "jit(run)/alea/clock/add:" } }
    events { metadata_id: 3 offset_ps: 8000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stats { metadata_id: 7
            str_value: "jit(run)/while/body/vmap(alea/lookup)/gather:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3
    name: "%sample_attr.1 = f32[8,8]{1,0} custom-call(s32[1,8]{1,0} %a), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 7 ref_value: 9 } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(run)/while/body/alea/reduce/sample_attr/pallas_call:" } }
}
planes {
  name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = f32[8]{0} x"
    stats { metadata_id: 1 str_value: "jit(run)/alea/sensor/mul:" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


def _xplane(tmp_path, text):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_op_names_come_from_the_device_planes_event_metadata(tmp_path):
    """The profiler keeps an op's name on its event metadata, interned or
    not; an event's own stat and a host plane's metadata name nothing."""
    names = S.load_op_names(_xplane(tmp_path, XSPACE))
    assert names == {
        "fusion.1 f32[8]": "jit(run)/while/body/vmap(alea/lookup)/gather:",
        "sample_attr.1 f32[8,8] tpu_custom_call":
            "jit(run)/while/body/alea/reduce/sample_attr/pallas_call:"}


def test_a_label_of_two_stages_is_an_error(tmp_path):
    two = XSPACE.replace(
        'event_metadata { key: 2 value { id: 2\n'
        '    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" '
        '} }',
        'event_metadata { key: 2 value { id: 2\n'
        '    name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"\n'
        '    stats { metadata_id: 7 str_value: "jit(g)/alea/sensor/mul:" '
        '} } }')
    assert two != XSPACE
    with pytest.raises(ValueError):
        S.load_op_names(_xplane(tmp_path, two))


def test_first_call_cuts_the_window_at_the_next_launch_of_its_head():
    ops = [("fusion.0 u32[1]", 10.0, 2.0), ("fusion.5 f32[8]", 13.0, 5.0),
           ("fusion.0 u32[1]", 20.0, 2.0), ("fusion.5 f32[8]", 23.0, 5.0),
           ("copy.1 f32[8]", 5.0, 1.0)]                 # before the window
    spans = [("bench/window", 8.0, 40.0), ("bench/read", 15.0, 16.0),
             ("bench/read", 25.0, 26.0)]
    one = S.first_call(T.Trace(device_ops={PLANE: ops}, spans=spans))
    assert one.device_ops == {PLANE: ops[:2]}
    assert one.window() == (10.0, 20.0)
    assert one.spans == [("bench/window", 10.0, 20.0),
                         ("bench/read", 15.0, 16.0)]
