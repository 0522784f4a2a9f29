#!/usr/bin/env python3
"""Print what a profiler trace holds, for reading by hand: planes, their
lines, and the first events of each line.

    python3 benchmark/tests/dump_trace.py .bench_trace/<workload> [events per line]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmark import trace_reduce
    n = int(argv[2]) if len(argv) > 2 else 6
    for plane in trace_reduce.load(trace_reduce.find_xplane(argv[1])):
        print(f"PLANE {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            ev = line["events"]
            if not ev:
                continue
            total = sum(d for _, _, d in ev)
            print(f"  LINE {line['name']!r}: {len(ev)} events, "
                  f"sum {total:.6f} s")
            for name, s, d in ev[:n]:
                print(f"    {name[:100]!r} start {s:.6f} dur {d * 1e3:.4f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
