"""A look at a trace by hand: planes, lines, and the names with most time.

    python benchmark/tests/dump_trace.py <file.xplane.pb> [name-filter]
"""

from __future__ import annotations

import sys


def main(argv) -> int:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(argv[1])
    needle = argv[2] if len(argv) > 2 else ""
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            total: dict = {}
            count = 0
            for e in line.events:
                count += 1
                if needle in e.name:
                    slot = total.setdefault(e.name, [0, 0.0])
                    slot[0] += 1
                    slot[1] += e.duration_ns * 1e-9
            print(f"  line {line.name!r}: {count} events")
            for name, (n, seconds) in sorted(
                    total.items(), key=lambda kv: -kv[1][1])[:25]:
                print(f"    {seconds:10.6f}s  x{n:<6d} {name[:140]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
