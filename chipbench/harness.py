"""One run of one cell: set-up, the measured window, the check, the result.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run holds the chips of its cell in this one process.  It fails, with no
result, where JAX finds no TPU or fewer chips than the cell asks for.  Its
phases:

1. set-up (``setup_s``, from process start): the graph from the cache, the
   program's objects, compiles or cache loads, the cell's first steps,
   which run through the window's own call and feed and are kept for the
   check, and the warm-up steps that bring the path to its steady state;
2. the window: the path drives the program for ``--seconds``, and ends on
   ``block_until_ready`` of the last step's outputs.  No compile may land
   in it (counted with ``jax.monitoring``); with ``--trace 1`` the profiler
   records it;
3. the peak device memory is read, the program's state is dropped, and the
   plain reference replays the first steps; each number compared is printed
   beside its limit, last on standard error and last in the result line.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def parse_args(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Spans:
    """Host spans around the harness's calls into the program's layers.

    Each span is kept as ``(name, start, end)`` on the host clock and, while
    the profiler runs, written into its trace as ``harness.<name>``.  Loader
    threads record into the same list; ``list.append`` is atomic."""

    def __init__(self):
        self.events = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("harness." + name):
            yield
        self.events.append((name, t0, time.perf_counter()))

    def durations(self, name: str, t0: float, t1: float) -> list:
        return [b - a for n, a, b in list(self.events)
                if n == name and t0 <= a and b <= t1]


class CompileCounter:
    """Ends of every trace and backend compile (or compile-cache load) JAX
    reports from registration on."""

    def __init__(self):
        import jax
        self.ends = []
        self.compile_s = 0.0

        def on_duration(event, secs, **_):
            if event in COMPILE_EVENTS:
                self.ends.append(time.perf_counter())
                if event == COMPILE_EVENTS[0]:
                    self.compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ends if t0 <= t <= t1)


class Context:
    """What a training path gets from the harness."""

    def __init__(self, registry, parts: dict, seed: int, spans: Spans):
        self.registry = registry
        self.cell = parts["cell"]
        self.config = parts["config"]
        self.mix = parts["mix"]
        self.reference = parts["reference"]
        self.seed = seed
        self.spans = spans
        self.cache_root = os.path.join(registry.dir, ".cache")
        self._arrays = None
        self.graph_generated = None
        self.memo = {}

    def graph_arrays(self) -> dict:
        from chipbench import graphs
        if self._arrays is None:
            with self.spans("setup.graph"):
                self._arrays, self.graph_generated = graphs.load_arrays(
                    self.config["name"], self.config["graph"],
                    self.cache_root)
        return self._arrays

    def program_graph(self):
        from chipbench import graphs
        return graphs.to_program_graph(self.graph_arrays(),
                                       self.config["graph"]["classes"])

    def init_params(self, seed: int):
        """The initial weights, made on the device in one jitted call."""
        import jax
        import numpy as np
        # any whole number, also past 32 bits, maps to one 32-bit key seed
        key_seed = int(np.random.SeedSequence(seed).generate_state(1)[0])
        init = jax.jit(lambda k: self.reference.init(self.config, k))
        return init(jax.random.PRNGKey(key_seed))


def enable_compile_cache(bench_dir: str) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        bench_dir, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(cell: dict) -> tuple:
    """``(devices, error)``: the devices JAX sees, and why the cell cannot
    run on them (``None`` when it can)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return devices, (f"needs a TPU, JAX found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < cell["chips"]:
        return devices, (f"needs {cell['chips']} chips, JAX found "
                         f"{len(devices)}")
    return devices, None


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def pace_note(spans: Spans, window: dict, t_start: float) -> str:
    """One line on the steadiness of the window: the intervals between the
    ends of successive step dispatches, their median and the longest, and
    where in the window that one ended."""
    import statistics
    t0 = window["t0"]
    ends = sorted(b for n, a, b in list(spans.events)
                  if n == "dispatch" and t0 <= a and b <= window["t1"])
    if not ends:
        return "pace: no step dispatched in the window"
    gaps = [b - a for a, b in zip([t0] + ends, ends)]
    k = max(range(len(gaps)), key=gaps.__getitem__)
    return (f"pace: {len(gaps)} dispatches, interval median "
            f"{1e3 * statistics.median(gaps):.3f} ms, longest "
            f"{1e3 * gaps[k]:.3f} ms ending {ends[k] - t0:.3f} s into the "
            f"window; window opened {t0 - t_start:.3f} s after "
            f"the process started")


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def main(argv=None, *, root: str = None, require_chip: bool = True,
         t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    from chipbench.registry import Registry
    registry = Registry(root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parts = registry.resolve(args.workload)
    cell = parts["cell"]

    import jax
    devices, why = find_chips(cell)
    if why and require_chip:
        print(f"chipbench: {args.workload}: {why}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    src = os.path.join(registry.root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    cache_dir = enable_compile_cache(registry.dir)
    print(f"cell {args.workload}: config {cell['config']}, traffic "
          f"{cell['traffic']}, path {parts['mix']['path']}; device "
          f"{devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", file=sys.stderr)
    with jax.default_matmul_precision(parts["config"]["matmul_precision"]):
        return _run(args, registry, parts, devices, t_start)


def _run(args, registry, parts, devices, t_start) -> int:
    import jax
    cell = parts["cell"]
    counter = CompileCounter()
    spans = Spans()
    ctx = Context(registry, parts, args.seed, spans)
    path = parts["path"]
    session = path.Session(ctx)
    first = session.start(args.seed)

    trace_dir = None
    if args.trace:
        from chipbench import trace_reduce
        # the cell's latest trace stays for reading by hand
        # (``chipbench/trace_dump.py``); the next traced run replaces it
        trace_dir = os.path.join(registry.dir, ".cache", "traces",
                                 args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profile_options())
    try:
        # after the trace has started, so that the window opens on the
        # steady state that the warm-up reached
        session.warm()
        setup_s = time.perf_counter() - t_start
        window = session.window(args.seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    compiles = counter.between(window["t0"], window["t1"])
    print(pace_note(spans, window, t_start), file=sys.stderr)
    memory = peak_memory(devices)
    session.close()
    del session

    numbers = path.check(ctx, first)
    numbers["compiles_in_window"] = compiles
    limits = parts["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(_finite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    correct = correct and window["failed"] == 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": window["steps"],
              "failed": window["failed"]}
    if args.trace:
        from chipbench import trace_reduce
        run = trace_reduce.reduce_run(registry, parts, ctx, window, trace_dir,
                                      devices)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        metrics = {}
        for spec, reader in parts["per_layer"]:
            value = reader.read(run)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        for line in run.notes:
            print(line, file=sys.stderr)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = run.trace["breakdown"]
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in parts["end_to_end"]}
        result["device"] = device
    print(f"setup {setup_s:.3f} s (graph "
          f"{'generated' if ctx.graph_generated else 'from the cache'}, "
          f"{counter.compile_s:.3f} s compiling); window "
          f"{window['t1'] - window['t0']:.3f} s, {window['steps']} steps",
          file=sys.stderr)
    result["checks"] = checks
    for k in sorted(set(numbers) - set(limits)):
        print(f"reading {k} {numbers[k]!r} (not compared)", file=sys.stderr)
    print(f"correct {bool(correct)}; the numbers compared and their limits:",
          file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0
