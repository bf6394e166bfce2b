"""The program's spans and scopes read from a trace: scope labels, the map
from compiled instructions to ``op_name``, the reduction of a recorded CPU
trace, and every reader of a program span or scope on a synthetic run."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import program_trace as P
from chipbench import trace_reduce as TR

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


@pytest.mark.parametrize("op_name, label", [
    ("jit(step)/jvp(gnn.aggregate)/pallas_unfused/jit(_gss_unfused_jit)/"
     "pallas_call", "gnn.aggregate/pallas_unfused fwd"),
    ("jit(step)/transpose(jvp(gnn.aggregate))/jax_ops/jit(_take)/scatter-add",
     "gnn.aggregate/jax_ops bwd"),
    ("jit(step)/jvp(gnn.dense)/dot_general", "gnn.dense fwd"),
    ("jit(step)/transpose(jvp(gnn.dense))/transpose", "gnn.dense bwd"),
    ("jit(step)/optimizer/sub", "optimizer fwd"),
    ("jit(_block_degrees)/graph.degrees/scatter-add", "graph.degrees fwd"),
    ("jit(step)/jvp(gnn.aggregate)/gnn.aggregate/jax_ops/mul",
     "gnn.aggregate/jax_ops fwd"),
    ("gather", None),
    ("jit(step)/jvp(jit(_gss_unfused_jit))/pallas_call", None),
])
def test_scope_labels(op_name, label):
    assert P.scope_label(op_name) == label


def _scoped_step(w, x):
    def loss(w):
        with jax.named_scope("gnn.dense"):
            h = x @ w
        with jax.named_scope("gnn.aggregate"):
            with jax.named_scope("jax_ops"):
                h = jnp.sin(h) * 2.0
        with jax.named_scope("gnn.loss"):
            return jnp.sum(h ** 2)

    val, g = jax.value_and_grad(loss)(w)
    with jax.named_scope("optimizer"):
        return w - 0.1 * g, val


def test_compiled_instructions_map_to_their_op_names():
    w, x = jnp.ones((32, 32)), jnp.ones((16, 32))
    text = jax.jit(_scoped_step).lower(w, x).compile().as_text()
    names = P.hlo_op_names(text)
    labels = {P.scope_label(v) for v in names.values()}
    assert {"gnn.dense fwd", "gnn.dense bwd", "gnn.aggregate/jax_ops fwd",
            "optimizer fwd"} <= labels
    # every fusion of the entry computation gets an op_name
    entry = text[text.index("ENTRY"):]
    fusions = [l.split("=")[0].strip().lstrip("ROOT ").lstrip("%")
               for l in entry.splitlines() if " fusion(" in l]
    assert fusions and all(f in names for f in fusions)


def test_fusion_without_metadata_takes_its_computations():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->f32[]}",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %p = f32[4] parameter(0)',
        '  %a = f32[4] add(%p, %p), metadata={op_name="jit(step)/jvp(gnn.norm)/add"}',
        '  ROOT %m = f32[4] multiply(%a, %a)',
        "}",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        "  %x = f32[4] parameter(0)",
        "  ROOT %fusion.3 = f32[4] fusion(%x), kind=kLoop, calls=%fused_computation.1",
        "}"])
    assert P.hlo_op_names(text)["fusion.3"] == "jit(step)/jvp(gnn.norm)/add"


def _op(name, s, e, module="jit_step"):
    return P.DeviceOp(s, e, module, name)


def _span(name, s, e, thread="main", **attrs):
    return P.Span(name, s, e, thread, attrs)


def test_operations_take_the_module_running_at_their_start():
    """On a TPU the op events carry no module; the plane's module line
    tells which program each ran in."""
    ops = [_op("fusion", 10, 20, module=""), _op("fusion", 120, 130, module=""),
           _op("fusion.9", 140, 150, module="jit_other"),
           _op("copy.1", 95, 99, module="")]
    modules = [(100, 200, P.module_key("jit__block_degrees(1084915)")),
               (0, 90, P.module_key("jit_step(14015961154084503345)"))]
    P.assign_modules(ops, modules)
    assert [o.module for o in ops] == ["jit_step", "jit__block_degrees",
                                       "jit_other", ""]


def test_reduction_labels_operations_and_idle_gaps():
    hlo = {"jit_step": {"fusion.1": "jit(step)/jvp(gnn.aggregate)/jax_ops/mul",
                        "fusion.2": "jit(step)/optimizer/sub"}}
    ops = {"/device:TPU:0": [
        _op("fusion.1", 100, 300),
        _op("copy.5", 300, 350),
        _op("fusion.2", 350, 400),
        _op("scatter.1", 600, 650, module="jit__block_degrees")]}
    host = [_span("harness.window", 0, 1000),
            _span("harness.fetch", 400, 900),
            _span("repro.store.fetch_masked", 420, 560, rows=4, pad_rows=1,
                  bytes=64, cpu=1e-7),
            _span("repro.sampler.sample", 0, 900, thread="loader", cpu=4e-7),
            _span("repro.loader.get", 900, 1200, queue="empty", cpu=0.0)]
    pt = P.reduce_program(ops, host, hlo)
    ns = {k: round(v * 1e9) for k, v in pt.device_s.items()}
    assert ns == {"gnn.aggregate/jax_ops fwd": 200, "(no scope)": 50,
                  "optimizer fwd": 50, "jit(_block_degrees)": 50}
    assert pt.scope_s("gnn.aggregate") == pytest.approx(200e-9)
    assert pt.scoped_s() == pytest.approx(300e-9) and pt.has_scopes()
    idle = {k: round(v * 1e9) for k, v in pt.idle_s.items()}
    # [0, 100) before any span on the main thread; [400, 600): midpoint 500
    # inside the store's gather; [650, 1000): midpoint 825, the upload
    assert idle == {"untraced host": 100, "repro.store.fetch_masked": 200,
                    "harness.fetch": 350}
    # the loader's get ends after the window: it is not the window's
    assert [s.name for s in pt.spans] == ["store.fetch_masked",
                                          "sampler.sample"]


def test_a_recorded_trace_is_reduced_by_scope(tmp_path):
    """On the CPU the operations sit on a host plane, beside the spans;
    the table is pointed at it, as in ``test_bench_trace_reduce``."""
    from repro.core import telemetry
    w, x = jnp.ones((64, 64)), jnp.ones((32, 64))
    step = jax.jit(_scoped_step)
    compiled_text = step.lower(w, x).compile().as_text()
    step(w, x)[0].block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=TR.profile_options())
    with jax.profiler.TraceAnnotation("harness.window"):
        for _ in range(3):
            with telemetry.span("store.fetch_masked", rows=2) as attrs:
                attrs["bytes"] = 8
            w, _ = step(w, x)
        w.block_until_ready()
    jax.profiler.stop_trace()
    table = dict(TR.load_table(), device_plane_prefix="/host:CPU",
                 op_lines="XLA")
    path = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    ops, host = P.read_trace(path, table, chips=1)
    assert ops and any(o.module == "jit__scoped_step"
                       for p in ops.values() for o in p)
    hlo = {"jit__scoped_step": P.hlo_op_names(compiled_text)}
    pt = P.reduce_program(ops, host, hlo)
    # the CPU fuses the forward aggregation into the loss
    assert {"gnn.dense fwd", "gnn.dense bwd", "gnn.aggregate/jax_ops bwd",
            "optimizer fwd"} <= set(pt.device_s)
    fetches = pt.named("store.fetch_masked")
    assert len(fetches) == 3
    assert all(s.attrs["rows"] == 2 and s.attrs["bytes"] == 8
               and s.attrs["cpu"] >= 0 for s in fetches)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(program):
    run = TR.Run(cell={"name": "x"}, config={}, mix={}, reference=None,
                 window={"t0": 0.0, "t1": 1.0, "steps": 2}, spans=None,
                 trace={"planes": 1}, peak={}, chips=1, notes=[])
    run.program_trace = program
    return run


MS = 1e6     # ns


def _program():
    spans = [_span("loader.get", 0, 1, queue="empty", cpu=0.0),
             _span("loader.get", 0, 1, queue="ready", cpu=0.0),
             _span("loader.get", 0, 1, queue="empty", cpu=0.0),
             _span("loader.get", 0, 1, queue="empty", cpu=0.0),
             _span("sampler.sample", 0, 400 * MS, cpu=0.1),
             _span("sampler.sample", 0, 600 * MS, cpu=0.4),
             _span("store.fetch_masked", 0, 300 * MS, cpu=0.24, rows=100,
                   pad_rows=40, bytes=3 * 2**20),
             _span("store.fetch_masked", 0, 500 * MS, cpu=0.4, rows=100,
                   pad_rows=60, bytes=3 * 2**20)]
    return P.ProgramTrace(spans, {"gnn.aggregate/pallas_unfused fwd": 0.6,
                                  "gnn.aggregate/pallas_unfused bwd": 0.2,
                                  "gnn.dense fwd": 0.15, "(no scope)": 0.05},
                          {})


@pytest.mark.parametrize("name, value", [
    ("loader_empty_share", 75.0),
    ("sample_offcpu_share", 50.0),
    ("rows_gather_ms", 400.0),
    ("gather_offcpu_share", 20.0),
    ("input_rows_mib_per_step", 3.0),
    ("pad_row_share", 50.0),
    ("aggregation_ms", 400.0),
    ("scoped_share", 95.0),
])
def test_each_program_reader(name, value):
    reader = _reader(name)
    assert reader.read(_run(_program())) == pytest.approx(value)
    # no trace to read, or a program that writes no span and no scope
    assert reader.read(_run(None)) is None
    bare = P.ProgramTrace([], {"(no scope)": 1.0, "jit(add)": 0.1}, {})
    assert reader.read(_run(bare)) is None


def test_no_device_plane_reads_nothing(tmp_path):
    run = TR.Run(cell={"name": "x"}, config={}, mix={}, reference=None,
                 window={"t0": 0.0, "t1": 1.0, "steps": 2}, spans=None,
                 trace={"planes": 0}, peak={}, chips=1, notes=[])
    assert P.of(run, str(tmp_path)) is None and run.notes == []
