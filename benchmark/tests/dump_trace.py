"""Look at a trace by hand: ``python3 benchmark/tests/dump_trace.py <file.xplane.pb>``
prints every plane and line with its event count and first events."""

import sys

from jax.profiler import ProfileData


def main(path, show=4):
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:show]:
                print("     ", ev.name[:100], ev.start_ns, ev.duration_ns)


if __name__ == "__main__":
    main(sys.argv[1])
