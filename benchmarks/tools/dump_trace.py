"""Look at one profiler trace by hand: its planes, lines and longest
events, and the reduced form ``benchmarks/trace/xplane.py`` makes of it.

    python3 benchmarks/tools/dump_trace.py <trace dir> [reduced.json] [span ...]
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.trace import xplane     # noqa: E402


def main(argv) -> int:
    from jax.profiler import ProfileData
    path = xplane.find(argv[1])
    print(path, Path(path).stat().st_size, "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in sorted(events, key=lambda e: -e.duration_ns)[:6]:
                print(f"    {e.name[:90]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {dict(e.stats)!r:.400}")
    reduced = xplane.load(path, argv[3:])
    print("busy_s", xplane.busy_seconds(reduced))
    print("top ops", xplane.top_ops(reduced))
    print("idle gaps", xplane.idle_gaps(reduced))
    if len(argv) > 2:
        for dev in reduced["devices"]:      # a sample a test can hold
            dev["ops"] = dev["ops"][:4000]
            dev["modules"] = dev["modules"][:200]
        Path(argv[2]).parent.mkdir(parents=True, exist_ok=True)
        Path(argv[2]).write_text(json.dumps(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
