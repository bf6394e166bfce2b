"""The program's own spans and scopes in a traced run.

The program writes its host spans into the profiler's trace while it is
recorded (``repro.<name>``, with their attributes and ``cpu``, the
thread's CPU seconds over the span; ``repro.core.telemetry``), and names
its device work with ``jax.named_scope`` (``gnn.aggregate`` with the
implementation under it, ``gnn.dense``, ``gnn.norm``, ``gnn.loss``,
``optimizer``, ``graph.degrees``), which reaches each compiled
instruction's ``op_name``.  :func:`of` reads both from the run's trace,
once per run, for the readers in ``metrics/``:

* the ``repro.`` spans inside the window, each with its thread and
  attributes;
* device seconds by program scope, forward apart from backward: the trace
  names each device operation's instruction and, by time, its module;
  its ``op_name`` comes from the step's compiled program, lowered again
  at the cell's shapes after the window; an operation of another program
  (a jitted helper such as ``_block_degrees``) is named by its module;
* idle seconds by program span: each gap in the device's work is given
  the innermost ``repro.`` or ``harness.`` span on the main thread at its
  midpoint.

Two notes go to the run's standard error: ``trace: device seconds by
scope`` and ``trace: idle seconds by program span``.

A trace without a device plane (a run on the CPU) gives nothing: these
numbers describe a run on the chip, next to its device timeline.  A
program that writes no ``repro.`` spans gives spans of none, and its
readers then report nothing.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import json
import os
import re

from chipbench import trace_reduce

SCOPE = re.compile(r"^(?:gnn\.\w+|graph\.\w+|optimizer)$")
IMPLS = {"jax_ops", "pallas_unfused", "pallas_fused", "gat_fused",
         "gat_multipass"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
UNSCOPED = "(no scope)"
# the device plane's line of whole-program executions (``jit_step(<id>)``):
# on a TPU an operation's stats do not name its module, its time does
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass
class Span:
    name: str            # without the ``repro.`` prefix
    start: float         # ns, the profiler's clock
    end: float
    thread: str
    attrs: dict


@dataclasses.dataclass
class ProgramTrace:
    spans: list                  # [Span] of ``repro.`` inside the window
    device_s: dict               # {scope label: device seconds}
    idle_s: dict                 # {innermost main-thread span: idle s}

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def scoped_s(self) -> float:
        return sum(v for k, v in self.device_s.items() if k != UNSCOPED)

    def has_scopes(self) -> bool:
        """Whether any operation carries a program scope."""
        return any(k != UNSCOPED and not k.startswith("jit(")
                   for k in self.device_s)

    def scope_s(self, scope: str) -> float:
        """Device seconds under ``scope``, forward and backward."""
        return sum(v for k, v in self.device_s.items()
                   if k.split(" ")[0].split("/")[0] == scope)


def scope_label(op_name: str):
    """``"<scope>[/<implementation>] fwd|bwd"`` for an ``op_name`` under
    a program scope, else ``None``.  The backward pass wraps the scope in
    ``transpose(...)``."""
    top, impl, bwd = None, None, False
    for part in op_name.split("/"):
        inner = part
        while True:
            m = re.fullmatch(r"[\w.]+\((.*)\)", inner)
            if not m:
                break
            inner = m.group(1)
        if top is None and SCOPE.match(inner):
            top, bwd = inner, part.startswith("transpose(")
        elif top is not None and inner in IMPLS:
            impl = inner
            break
    if top is None:
        return None
    return (top + ("/" + impl if impl else "")
            + (" bwd" if bwd else " fwd"))


def hlo_op_names(text: str) -> dict:
    """``{instruction: op_name}`` of one compiled module's HLO text.  A
    fusion without metadata takes that of its fused computation's root,
    else the first one inside it."""
    names, inside, calls = {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        m = INSTR.match(line)
        if not m:
            continue
        op = OP_NAME.search(line)
        if op:
            names[m.group(1)] = op.group(1)
            if comp is not None and (comp not in inside
                                     or line.lstrip().startswith("ROOT")):
                inside[comp] = op.group(1)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called:
            calls[m.group(1)] = called.group(1)
    for instr, comp in calls.items():
        if instr not in names and comp in inside:
            names[instr] = inside[comp]
    return names


def module_key(name: str) -> str:
    """A module's name without the suffixes a trace may add."""
    return re.sub(r"(\.\d+|\(\d+\))+$", "", str(name))


def step_hlo(run, bench_dir: str) -> dict:
    """``{module: {instruction: op_name}}`` of the cell's step, lowered and
    compiled again at the cell's shapes (as
    ``chipbench/tests/test_cell_compile.py`` does)."""
    import jax
    from chipbench.registry import Registry
    from repro.models.gnn import model as GM
    from repro.models.gnn.model import GNNConfig
    from repro.optim import AdamW

    cfg, mix = run.config, run.mix
    path = Registry(os.path.dirname(bench_dir)).path(mix["path"])
    m = cfg["model"]
    model = GNNConfig(arch=m["arch"], feat_dim=m["in_features"],
                      hidden=m["hidden"], num_classes=m["classes"],
                      num_layers=m["layers"], use_kernel=cfg["use_kernel"],
                      wire_codec=cfg.get("wire_codec", "fp32"))
    opt = AdamW(**cfg["optimizer"])
    step = getattr(GM, path.STEP_MAKER)(model, opt)
    params = jax.eval_shape(functools.partial(run.reference.init, cfg),
                            jax.random.PRNGKey(0))
    ostate = jax.eval_shape(opt.init, params)
    args = path.step_args(cfg, mix, jax.ShapeDtypeStruct)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        text = jax.jit(step).lower(params, ostate, *args).compile().as_text()
    head = re.match(r"HloModule\s+([\w.\-]+)", text)
    return {module_key(head.group(1) if head else ""): hlo_op_names(text)}


@dataclasses.dataclass
class DeviceOp:
    start: float         # ns
    end: float
    module: str          # "" until assigned
    instr: str           # the instruction's name in its module


def read_trace(path: str, table: dict, chips: int) -> tuple:
    """``(ops, host)`` of one ``.xplane.pb``: the operations on the device
    planes of the cell's ``chips`` (``<device_plane_prefix><id>``, ids
    below ``chips``), ``{plane: [DeviceOp]}``, and every ``harness.`` and
    ``repro.`` host span, ``[Span]`` with the prefix kept in the name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, host = {}, []
    prefix = table["device_plane_prefix"]
    for plane in pd.planes:
        rest = plane.name[len(prefix):]
        if plane.name.startswith(prefix) and (
                not rest.isdigit() or int(rest) < chips):
            out = ops.setdefault(plane.name, [])
            modules = []
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 module_key(e.name)) for e in line.events]
                if not re.search(table["op_lines"], line.name):
                    continue
                for e in line.events:
                    out.append(device_op(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         dict(e.stats)))
            assign_modules(out, modules)
        if plane.name.startswith("/host"):
            # a line is one thread; threads may share a name
            for k, line in enumerate(plane.lines):
                thread = f"{plane.name}#{k}"
                for e in line.events:
                    if e.name.startswith(("repro.", "harness.")):
                        host.append(Span(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns, thread,
                                         dict(e.stats)))
    return ops, host


def device_op(name: str, start: float, end: float, stats: dict) -> DeviceOp:
    """One device operation.  On a TPU its event is named by its HLO text
    (``%fusion.9 = ...``) and carries no module; on the CPU its stats name
    both."""
    instr = stats.get("hlo_op")
    if not instr:
        m = INSTR.match(name)
        instr = m.group(1) if m else name
    return DeviceOp(start, end, module_key(stats.get("hlo_module", "")),
                    str(instr))


def assign_modules(ops: list, modules: list) -> None:
    """Give each operation whose stats name no module the module whose
    execution ``(start, end, name)`` holds its start."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    for op in ops:
        k = bisect.bisect_right(starts, op.start) - 1
        if not op.module and k >= 0 and op.start < modules[k][1]:
            op.module = modules[k][2]


def label_ops(ops: list, hlo: dict) -> list:
    """The scope label of each operation.  Operations of a module in
    which none carries a program scope, and which is not the step, are
    named by their module's jitted function (``jit(_block_degrees)``);
    the rest of the step's are ``(no scope)``."""
    labels = []
    for op in ops:
        op_name = hlo.get(op.module, {}).get(op.instr, "")
        labels.append(scope_label(op_name) if op_name else None)
    scoped = {op.module for op, label in zip(ops, labels) if label}
    out = []
    for op, label in zip(ops, labels):
        if label is None and op.module and op.module not in scoped \
                and op.module not in hlo:
            fn = op.module[4:] if op.module.startswith("jit_") else op.module
            label = f"jit({fn})"
        out.append(label or UNSCOPED)
    return out


def reduce_program(ops: dict, host: list, hlo: dict) -> ProgramTrace:
    """Program spans, device seconds by scope and idle seconds by program
    span, over the last ``harness.window`` span (or the whole trace)."""
    windows = [(s.start, s.end) for s in host if s.name == "harness.window"]
    main = {s.thread for s in host if s.name == "harness.window"}
    if windows:
        t0, t1 = windows[-1]
    else:
        starts = [o.start for p in ops.values() for o in p]
        ends = [o.end for p in ops.values() for o in p]
        t0, t1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    spans = [dataclasses.replace(s, name=s.name[len("repro."):])
             for s in host if s.name.startswith("repro.")
             and t0 <= s.start and s.end <= t1]
    device_s = collections.Counter()
    idle_s = collections.Counter()
    on_main = [s for s in host if not main or s.thread in main]
    on_main = [s for s in on_main if s.name != "harness.window"]
    for plane in ops.values():
        clipped = [dataclasses.replace(o, start=max(o.start, t0),
                                       end=min(o.end, t1))
                   for o in plane if o.end > t0 and o.start < t1]
        for o, label in zip(clipped, label_ops(clipped, hlo)):
            device_s[label] += (o.end - o.start) * 1e-9 / len(ops)
        merged = trace_reduce.union((o.start, o.end) for o in clipped)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle_s[innermost(0.5 * (s + e), on_main)] += (
                    (e - s) * 1e-9 / len(ops))
    return ProgramTrace(spans, dict(device_s), dict(idle_s))


def innermost(t: float, spans: list) -> str:
    inside = [(s.end - s.start, s.name) for s in spans
              if s.start <= t <= s.end]
    return min(inside)[1] if inside else "untraced host"


def of(run, bench_dir: str):
    """The :class:`ProgramTrace` of ``run``, read once and kept as
    ``run.program_trace``; ``None`` where the run's trace holds no device
    plane or cannot be found."""
    if "program_trace" in vars(run):
        return run.program_trace
    result = None
    trace_dir = os.path.join(bench_dir, ".cache", "traces", run.cell["name"])
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if run.trace.get("planes") and files:
        table = trace_reduce.load_table()
        ops, host = read_trace(max(files, key=os.path.getmtime), table,
                               run.chips)
        hlo = {}
        try:
            hlo = step_hlo(run, bench_dir)
        except Exception as e:      # noqa: BLE001 — a reader never fails a run
            run.notes.append(f"trace: the step's program could not be "
                             f"lowered again ({type(e).__name__}: {e}); "
                             f"its operations count as unscoped")
        result = reduce_program(ops, host, hlo)
        run.notes.append("trace: device seconds by scope "
                         + json.dumps(result.device_s, sort_keys=True))
        run.notes.append("trace: idle seconds by program span "
                         + json.dumps(result.idle_s, sort_keys=True))
    run.program_trace = result
    return result


def program(run, reader_file: str):
    """:func:`of` for the reader in ``reader_file``, a file in
    ``<bench>/metrics/``."""
    return of(run, os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


def spans(run, reader_file: str, name: str) -> list:
    """The ``repro.<name>`` spans of ``run``'s window (see
    :func:`program`)."""
    pt = program(run, reader_file)
    return pt.named(name) if pt else []


def offcpu_share(spans: list):
    """``1 - sum(cpu) / sum(wall)`` of ``spans``, in %: the share of their
    time their threads were not running."""
    wall = sum(s.end - s.start for s in spans) * 1e-9
    if not spans or wall <= 0:
        return None
    return 100.0 * (1.0 - sum(float(s.attrs["cpu"]) for s in spans) / wall)
