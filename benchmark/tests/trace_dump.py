"""Look at a trace by hand before writing code against it: the planes,
their lines, how many events each holds and the first few names.

    python benchmark/tests/trace_dump.py <file.xplane.pb> [events per line]
"""

import sys

from jax.profiler import ProfileData


def main(path: str, show: int = 4) -> None:
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{lo:.0f}..{hi:.0f} ns")
            for e in events[:show]:
                print(f"      {e.name[:90]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
