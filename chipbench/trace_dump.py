"""Print what a profiler trace holds, to write ``op_layers.json`` against.

    python3 chipbench/trace_dump.py <trace dir> [events per line]

Lists every plane and line with its event count, then the longest events of
each TPU plane's lines and the harness's host spans, with their metadata.
"""
import collections
import glob
import os
import sys


def main(argv):
    from jax.profiler import ProfileData
    trace_dir = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 40
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: " + ", ".join(
            f"{l.name} ({len(list(l.events))})" for l in lines))
        for line in lines:
            events = list(line.events)
            if plane.name.startswith("/device"):
                total = collections.Counter()
                for e in events:
                    total[e.name] += e.duration_ns
                print(f"  LINE {line.name}: top by total ns")
                for name, ns in total.most_common(top):
                    print(f"    {ns:>14.0f} {name}")
                for e in sorted(events, key=lambda e: -e.duration_ns)[:5]:
                    print(f"    longest {e.duration_ns:.0f} {e.name} "
                          f"{dict(e.stats)}"[:1500])
            else:
                spans = [e for e in events if e.name.startswith("harness.")]
                if spans:
                    print(f"  LINE {line.name}: {len(spans)} harness spans, "
                          f"first {[(e.name, e.start_ns, e.duration_ns) for e in spans[:8]]}")


if __name__ == "__main__":
    main(sys.argv[1:])
