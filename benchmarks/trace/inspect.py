"""Print what a `.xplane.pb` holds, for reading one by hand before trusting
reduce.py with it: planes, their lines, and on each line the names that
took most time, with the statistics one event carries.

    python -m benchmarks.trace.inspect <file.xplane.pb> [names per line]
"""
import collections
import sys

from .reduce import load


def main(path, top=12):
    for plane in load(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            secs, count = collections.Counter(), collections.Counter()
            for e in events:
                secs[e.name] += e.duration_ns * 1e-9
                count[e.name] += 1
            t0 = min(e.start_ns for e in events) * 1e-9
            t1 = max(e.start_ns + e.duration_ns for e in events) * 1e-9
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(secs)} names, {t0:.6f}..{t1:.6f} s")
            for name, s in secs.most_common(top):
                print(f"    {s:10.6f} s {count[name]:7d} x  {name[:100]}")
            print(f"    stats of one event: {dict(events[0].stats)}")


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
