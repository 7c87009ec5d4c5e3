"""The op events' HLO ``op_name``s and the training step read by them
(``harness/trace_ops.py``): the trainer's phase and node names, a
synthetic trace written as the profiler writes one, and the recorded
fixture, on which the one reading gives what ``trace.read_xplane``
gives."""
import os

import pytest

from benchmark.harness import spec, trace, trace_ops

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "serve-batch.xplane.pb")
MS = 1_000_000
PROGRAM = "jit_wrapped(7)"

#: one step of the synthetic program: (instruction, ms, op_name); 29.6 of
#: its 30 ms hold ops
STEP = [
    ("fusion.1", 8.0, "jit(wrapped)/jvp(forward)/conv1/conv_general_dilated"),
    ("all-reduce.1", 2.0, "jit(wrapped)/jvp(forward)/layer0_proj/dot_general"),
    ("fusion.2", 10.0,
     "jit(wrapped)/transpose(jvp(forward))/conv1/conv_general_dilated"),
    ("all-reduce.2", 3.0,
     "jit(wrapped)/transpose(jvp(forward))/embed/jit(_take)/scatter-add"),
    ("fusion.3", 1.0, "jit(wrapped)/transpose(jvp(forward))/jvp(forward)/"
                      "checkpoint/layer1_q/dot_general"),
    ("fusion.4", 2.0, "jit(wrapped)/update/mul"),
    ("reshape.5", 3.0, "jit(wrapped)/reshape"),
    ("copy.6", 0.6, ""),
]
AXES = {"model": {"all-reduce.1": 1 << 20}, "data": {"all-reduce.2": 3 << 20}}


def _xplane_pb2():
    space = trace_ops.load_xspace(b"")
    if space is None:
        pytest.skip("no xplane_pb2 installed")
    return type(space)


def synthetic() -> bytes:
    """Two chips, a 100 ms window (``bench.window``): the step program
    runs at 5-35 and 40-70 ms, and from 90 ms past the window's end (its
    first op inside); another program at 72-80 ms.  Each step's ops are
    :data:`STEP`, under a ``while`` container that spans the run."""
    XSpace = _xplane_pb2()
    space = XSpace()
    for chip in (0, 1):
        plane = space.planes.add(name=f"/device:TPU:{chip}")
        plane.stat_metadata[1].name = "tf_op"
        ids = {}

        def meta(name, op=None):
            if name not in ids:
                mid = ids[name] = len(ids) + 1
                plane.event_metadata[mid].id = mid
                plane.event_metadata[mid].name = name
                if op:
                    st = plane.event_metadata[mid].stats.add(metadata_id=1)
                    st.str_value = op
            return ids[name]

        mods = plane.lines.add(name="XLA Modules", timestamp_ns=0)
        ops = plane.lines.add(name="XLA Ops", timestamp_ns=0)
        for start in (5.0, 40.0, 90.0):
            mods.events.add(metadata_id=meta(PROGRAM),
                            offset_ps=int(start * MS * 1000),
                            duration_ps=int(30 * MS * 1000))
            ops.events.add(metadata_id=meta("while.1"),
                           offset_ps=int(start * MS * 1000),
                           duration_ps=int(30 * MS * 1000))
            t = start
            for name, ms, op in STEP:
                if start == 90.0 and name.startswith("all-reduce"):
                    t += ms              # the cut run holds no collective
                    continue
                ops.events.add(metadata_id=meta(f"%{name} = f32[8] x()", op),
                               offset_ps=int(t * MS * 1000),
                               duration_ps=int(ms * MS * 1000))
                t += ms
        mods.events.add(metadata_id=meta("jit_other(9)"),
                        offset_ps=72 * MS * 1000, duration_ps=8 * MS * 1000)
        ops.events.add(metadata_id=meta("%fusion.9 = f32[8] y()", "jit(other)/add"),
                       offset_ps=72 * MS * 1000, duration_ps=8 * MS * 1000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench.window"
    host.event_metadata[2].name = "bench.train_step"
    line = host.lines.add(name="python", timestamp_ns=0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=100 * MS * 1000)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=3 * MS * 1000)
    return space.SerializeToString()


@pytest.mark.parametrize("op_name,phase,node", [
    ("jit(wrapped)/jvp(forward)/stage1_unit1_conv1/conv_general_dilated",
     "forward", "stage1_unit1_conv1"),
    ("jit(wrapped)/transpose(jvp(forward))/stage4_unit3_bn2/mul",
     "backward", "stage4_unit3_bn2"),
    ("jit(wrapped)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "layer1_q/dot_general", "backward", "layer1_q"),
    ("jit(wrapped)/update/mul", "update", "mul"),
    ("jit(wrapped)/update/jit(_where)/select_n", "update", "select_n"),
    ("jit(wrapped)/guard/reduce_sum", "guard", "reduce_sum"),
    ("jit(wrapped)/shard_map/grad_reduce/psum", "grad_reduce", "psum"),
    # a symbol node named like a phase is still the forward's node
    ("jit(wrapped)/jvp(forward)/update/add", "forward", "update"),
    ("jit(wrapped)/update/mul;jit(wrapped)/jvp(forward)/fc/dot",
     "update", "mul"),
    ("jit(wrapped)/reshape", "", ""),
    ("", "", ""),
])
def test_phase_and_node_of_an_op_name(op_name, phase, node):
    assert trace_ops.phase_of(op_name) == phase
    assert trace_ops.node_of(op_name) == node


def test_one_reading_gives_the_harness_trace(tmp_path):
    raw = synthetic()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    ot = trace_ops.read(raw)
    assert ot.trace == trace.read_xplane(str(path))
    assert trace.summarize(ot.trace) == trace.summarize(
        trace.read_xplane(str(path)))
    ops = {name: op for name, _, _, op in ot.ops[0]}
    assert ops["fusion.2"] == STEP[2][2] and ops["copy.6"] == ""


def test_step_split_by_phase_node_and_axis():
    ot = trace_ops.read(synthetic())
    split = trace_ops.step_split(ot, collectives=AXES)
    assert split["program"] == PROGRAM
    assert split["runs"] == 2 and split["chips"] == 2
    assert split["run_ms_median"] == pytest.approx(30.0)
    ph = split["phase_ms"]
    assert ph["forward"] == pytest.approx(10.0)
    assert ph["backward"] == pytest.approx(14.0)      # remat's 1 ms inside
    assert ph["update"] == pytest.approx(2.0)
    assert ph[""] == pytest.approx(3.6)
    assert split["nodes"]["backward"] == pytest.approx(
        {"conv1": 10.0, "embed": 3.0, "layer1_q": 1.0})
    assert split["unowned"] == pytest.approx({"reshape": 3.0, "copy": 0.6})
    assert split["collective_ms"] == pytest.approx(
        {("model", "forward"): 2.0, ("data", "backward"): 3.0})
    vals = trace_ops.metric_values(split)
    assert vals == pytest.approx({
        "train_fwd_ms": 10.0, "train_bwd_ms": 14.0, "train_update_ms": 2.0,
        "train_unowned_ms": 3.6, "collective_ms.model": 2.0,
        "collective_ms.data": 3.0})


def test_the_phases_sum_to_the_step_and_the_axes_to_its_collectives():
    """The checks a reader of these numbers makes: the four phase numbers
    within 2 % of the step program's median run, and the two axes within
    2 % of the collective time ``collective_exposed_share`` counts, a
    step."""
    ot = trace_ops.read(synthetic())
    split = trace_ops.step_split(ot, collectives=AXES)
    vals = trace_ops.metric_values(split)
    phases = sum(vals[k] for k in ("train_fwd_ms", "train_bwd_ms",
                                   "train_update_ms", "train_unowned_ms"))
    assert abs(phases - split["run_ms_median"]) <= 0.02 * split["run_ms_median"]
    summary = trace.summarize(ot.trace)
    exposed_ms = 1e3 * sum(s for n, s in summary.op_seconds.items()
                           if trace_ops.COLLECTIVE.match(n))
    per_step = exposed_ms / split["runs"]
    axes = vals["collective_ms.model"] + vals["collective_ms.data"]
    assert abs(axes - per_step) <= 0.02 * per_step
    assert split["collective_all_ms"] == pytest.approx(axes)
    # without the span's mapping every collective's axis is unknown
    blind = trace_ops.step_split(ot)
    assert trace_ops.axis_totals(blind) == pytest.approx({"?": 5.0})


def test_fixture_reads_as_the_harness_reads_it():
    """The recorded serving trace: one reading gives every field of the
    summary the accepted metrics read, and its programs carry no trainer
    phase."""
    with open(FIXTURE, "rb") as f:
        ot = trace_ops.read(f.read())
    want = trace.summarize(trace.read_xplane(FIXTURE))
    got = trace.summarize(ot.trace)
    assert got == want
    split = trace_ops.step_split(ot)
    assert split["program"].startswith("jit_fn(")
    assert set(p for p, v in split["phase_ms"].items() if v) == {""}
    assert split["phase_ms"][""] == pytest.approx(split["run_ms_median"],
                                                  rel=0.02)
