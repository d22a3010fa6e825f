"""Print the structure of a profiler trace, for reading it by hand before
writing a reduction against it: planes, lines, the most frequent event
names per line, and the stats events carry.

    python3 bench/tools/dump_trace.py <trace dir>
"""

import collections
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.trace import find_xplane  # noqa: E402


def main(trace_dir: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            dur = collections.Counter()
            cnt = collections.Counter()
            for ev in evs:
                dur[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
            for name, ns in dur.most_common(25):
                print(f"    {ns / 1e6:12.3f} ms  x{cnt[name]:<6d} {name[:150]}")
            shown = 0
            for ev in evs:
                st = list(ev.stats)
                if st and shown < 4:
                    print("      stats:", [(k, str(v)[:160]) for k, v in st])
                    shown += 1


if __name__ == "__main__":
    main(sys.argv[1])
