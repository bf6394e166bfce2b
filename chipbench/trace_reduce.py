"""From the profiler's trace of the window to device time by layer.

The run writes an ``.xplane.pb`` under ``chipbench/.cache/traces/``.
:func:`load_events` reads it with ``jax.profiler.ProfileData`` into plain
lists: the device operations of each TPU plane, and the harness's host
spans (``harness.<name>``, from ``jax.profiler.TraceAnnotation``).  The
rest works on those lists alone:

* busy time: the union of the operations' intervals inside the window,
  per chip, averaged over the chips used; idle is the window less busy;
* time by layer: each operation's duration, attributed by the first rule of
  ``op_layers.json`` whose pattern matches its name or metadata; anything
  unmatched counts as ``other``;
* the breakdown: the operations that took most time, and the longest idle
  gaps, each named by the main-thread harness span in progress at its
  midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
# spans of the loader's threads and of the whole window say nothing about
# what the main thread was doing during a gap
NOT_MAIN = {"window", "sample"}
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start: float      # ns
    end: float        # ns
    meta: str         # name and metadata, for the layer rules


def profile_options():
    """The profiler's options for a traced run: device operations and the
    harness's ``TraceAnnotation`` spans, which the host tracer records at
    its lowest level, and no Python tracer.  At JAX's defaults the Python
    tracer records every Python call, which more than halves the speed of
    the program's Python sampler."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def load_table(path: str = None, edges=()) -> dict:
    """The op-to-layer table, with ``{edges}`` in a pattern standing for the
    cell's per-edge array lengths."""
    with open(path or os.path.join(HERE, "op_layers.json")) as f:
        table = json.load(f)
    alt = "(?:" + "|".join(str(int(e)) for e in edges) + ")" if edges else "(?!)"
    table["compiled"] = [(r["layer"],
                          re.compile(r["pattern"].replace("{edges}", alt)))
                         for r in table["rules"]]
    return table


def load_events(trace_dir: str, table: dict) -> tuple:
    """``(device_ops, host_spans)``: ``{plane: [Op]}`` for every TPU plane
    and ``[(name, start, end)]`` for every harness span."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}, []
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(table["device_plane_prefix"]):
            ops = []
            for line in plane.lines:
                if not re.search(table["op_lines"], line.name):
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    meta = " ".join([e.name] + [str(stats.get(k, ""))
                                                for k in table["meta_stats"]])
                    ops.append(Op(e.name, e.start_ns, e.start_ns
                                  + e.duration_ns, meta))
            device[plane.name] = ops
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("harness."):
                        host.append((e.name[len("harness."):], e.start_ns,
                                     e.start_ns + e.duration_ns))
    return device, host


def union(intervals) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(ops, t0: float, t1: float) -> list:
    return [Op(o.name, max(o.start, t0), min(o.end, t1), o.meta)
            for o in ops if o.end > t0 and o.start < t1]


def layer_of(op: Op, table: dict) -> str:
    for layer, pattern in table["compiled"]:
        if pattern.search(op.meta):
            return layer
    return "other"


def label_gap(s: float, e: float, host: list) -> str:
    mid = 0.5 * (s + e)
    inside = [(b - a, n) for n, a, b in host
              if n not in NOT_MAIN and a <= mid <= b]
    return min(inside)[1] if inside else "untraced host"


def reduce(device: dict, host: list, table: dict) -> dict:
    """Busy and idle time, time by layer and the breakdown, over the span
    named ``window`` (or the whole trace where there is none)."""
    windows = [(a, b) for n, a, b in host if n == "window"]
    if windows:
        t0, t1 = windows[-1]
    else:
        ends = [o.end for ops in device.values() for o in ops]
        starts = [o.start for ops in device.values() for o in ops]
        t0, t1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    window_s = (t1 - t0) * 1e-9
    planes = sorted(device)
    busy, layers = [], collections.Counter()
    by_op = collections.Counter()
    gaps = []
    for p in planes:
        ops = clip(device[p], t0, t1)
        merged = union((o.start, o.end) for o in ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for o in ops:
            dur = (o.end - o.start) * 1e-9
            layer = layer_of(o, table)
            layers[layer] += dur / len(planes)
            by_op[f"{layer}: {o.name}"] += dur / len(planes)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gaps.sort(reverse=True)
    idle_by_label = collections.Counter()
    for d, s, e in gaps:
        idle_by_label[label_gap(s, e, host)] += d * 1e-9
    return {
        "window_s": window_s, "busy_s": busy_s, "planes": len(planes),
        "layer_s": dict(layers), "idle_by_label": dict(idle_by_label),
        "breakdown": {
            "device_ops": [[n, s] for n, s in by_op.most_common(TOP)],
            "idle_gaps": [[label_gap(s, e, host), d * 1e-9]
                          for d, s, e in gaps[:TOP]]}}


def load_peak(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return peaks[device_kind]


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets."""
    cell: dict
    config: dict
    mix: dict
    reference: object
    window: dict
    spans: object
    trace: dict
    peak: dict
    chips: int
    notes: list

    def span_ms(self, name: str) -> list:
        return [1e3 * d for d in self.spans.durations(
            name, self.window["t0"], self.window["t1"])]

    def window_s(self) -> float:
        return self.window["t1"] - self.window["t0"]


def reduce_run(registry, parts, ctx, window, trace_dir, devices) -> Run:
    table = load_table(edges=window["edge_lengths"])
    device, host = load_events(trace_dir, table)
    used = {d for d in device
            if any(d.endswith(f":{dev.id}") for dev in devices)}
    trace = reduce({k: v for k, v in device.items() if k in used}, host,
                   table)
    notes = [f"trace: {trace['planes']} device plane(s), window "
             f"{trace['window_s']!r} s, busy {trace['busy_s']!r} s",
             "trace: device seconds by layer "
             + json.dumps(trace["layer_s"], sort_keys=True),
             "trace: idle seconds by host span "
             + json.dumps(trace["idle_by_label"], sort_keys=True)]
    return Run(cell=parts["cell"], config=parts["config"], mix=parts["mix"],
               reference=parts["reference"], window=window, spans=ctx.spans,
               trace=trace, peak=load_peak(devices[0].device_kind),
               chips=len(devices), notes=notes)
