"""Print what a profiler trace holds: planes, lines, the busiest op and
program names per device, and the benchmark's host spans.

  python bench/tools/trace_summary.py <log dir or .xplane.pb>
"""

import collections
import glob
import os
import sys


def main(path):
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print("file", path, os.path.getsize(path))
    pd = ProfileData.from_file(path)
    for pl in pd.planes:
        lines = list(pl.lines)
        print("PLANE", pl.name, [(ln.name, len(list(ln.events)))
                                 for ln in lines][:12])
        for ln in lines:
            evs = list(ln.events)
            if not evs:
                continue
            if pl.name.startswith("/device") or ln.name in ("XLA Ops",):
                c = collections.Counter()
                for e in evs:
                    c[e.name] += e.duration_ns
                print("  LINE", ln.name, "top:",
                      [(k, round(v / 1e6, 3)) for k, v in c.most_common(12)])
                e = evs[len(evs) // 2]
                try:
                    print("    sample", e.name, e.start_ns, e.duration_ns,
                          dict(e.stats))
                except Exception as x:  # noqa: BLE001
                    print("    sample", e.name, x)
            else:
                names = collections.Counter(e.name for e in evs
                                            if e.name.startswith("bench."))
                if names:
                    print("  LINE", ln.name, dict(names))


if __name__ == "__main__":
    main(sys.argv[1])
