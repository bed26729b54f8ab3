"""Look at a trace by hand: planes, lines, the commonest events with their
stats. ``python3 benchmark/inspect_trace.py <file.xplane.pb> [events per line]``"""
import collections
import sys

from jax.profiler import ProfileData


def main(path, top=12):
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            tot = collections.Counter()
            cnt = collections.Counter()
            example = {}
            for ev in evs:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                example.setdefault(ev.name, ev)
            span = (max(e.start_ns + e.duration_ns for e in evs)
                    - min(e.start_ns for e in evs))
            print(f"  LINE {line.name!r}: {len(evs)} events over "
                  f"{span / 1e6:.1f} ms")
            for name, ns in tot.most_common(top):
                stats = {k: (str(v)[:60]) for k, v in example[name].stats}
                print(f"    {ns / 1e6:10.3f} ms x{cnt[name]:<6} {name[:90]!r} "
                      f"{stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
