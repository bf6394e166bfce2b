"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 3] [--fault-seeds 3] [--out chiprun_out/x.json]

In one process, with one set of the program's objects: for each seed, the
cell's first steps through the program against the reference
(``program``); on the first ``--control-seeds`` seeds, the reference
computed at the next precision below the configuration's, put in the
program's place (``control``); on the first ``--fault-seeds`` seeds, the
program with half of each batch left out (``half_batch``).  A step that
returns its state unchanged reads 1 on ``grad_gap`` and ``update_gap`` by
their definition and needs no run.  Needs the cell's chips, as a run does.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the reference one precision below the configuration's: three bfloat16
# passes for float32 at ``highest``, as written out in the reference (the
# control of the tests) and as the TPU computes it natively
CONTROL = {"highest": {"control": "high", "control_native": "high_native"}}


def main(argv=None, *, root=None, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness
    from chipbench.registry import Registry

    registry = Registry(root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parts = registry.resolve(args.workload)
    devices, why = harness.find_chips(parts["cell"])
    if why and require_chip:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(registry.root, "src"))
    harness.enable_compile_cache(registry.dir)
    precision = parts["config"]["matmul_precision"]
    with jax.default_matmul_precision(precision):
        return _calibrate(args, registry, parts, precision)


def _calibrate(args, registry, parts, precision):
    import jax
    from chipbench import compare, faults, harness
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.Context(registry, parts, seeds[0], harness.Spans())
    path = parts["path"]
    session = path.Session(ctx)
    rows = []

    def record(kind, seed, numbers):
        row = {"kind": kind, "seed": seed, **numbers}
        rows.append(row)
        print(json.dumps(row), flush=True)

    t0 = time.perf_counter()
    for i, seed in enumerate(seeds):
        first = session.start(seed)
        session.stop()
        record("program", seed, path.check(ctx, first))
        if i < args.control_seeds:
            ref = path.reference_run(ctx, first, precision)
            for kind, prec in CONTROL[precision].items():
                ctl = path.reference_run(ctx, first, prec)
                record(kind, seed, compare.training_numbers(ctl, ref)[0])
    if args.fault_seeds:
        from repro.models.gnn import model as GM
        make = getattr(GM, path.STEP_MAKER)
        session.step = jax.jit(faults.faulty_step_maker(make, "half_batch")(
            session.model, session.opt))
        for seed in seeds[:args.fault_seeds]:
            first = session.start(seed)
            session.stop()
            record("half_batch", seed, path.check(ctx, first))
    session.close()
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        for k in ("loss_gap", "grad_gap", "update_gap",
                  "loss_gap_all_steps", "update_gap_median"):
            vals = [r[k] for r in rows if r["kind"] == kind]
            summary[f"{kind}.{k}"] = {"min": min(vals), "max": max(vals)}
    print(json.dumps({"workload": args.workload, "seconds":
                      time.perf_counter() - t0, "summary": summary}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
