"""Look at one capture by hand: planes, lines, event counts and the names that took most time.

``python3 -m perfbench.tools.inspect_trace <file.xplane.pb | trace dir> [top]``
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from perfbench.readers import xplane

    path = Path(argv[0])
    top = int(argv[1]) if len(argv) > 1 else 12
    pd = xplane.load(path if path.is_file() else xplane.find_xplane(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            sums, n, lo, hi = {}, 0, None, None
            for e in line.events:
                n += 1
                sums[e.name] = sums.get(e.name, 0.0) + e.duration_ns * 1e-9
                lo = e.start_ns if lo is None else min(lo, e.start_ns)
                hi = e.start_ns + e.duration_ns if hi is None else max(hi, e.start_ns + e.duration_ns)
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, {lo * 1e-9:.6f}s .. {hi * 1e-9:.6f}s")
            if plane.name.startswith("/device:") or "start_trace" in "".join(sums):
                for name, s in sorted(sums.items(), key=lambda kv: -kv[1])[:top]:
                    print(f"      {s:10.6f}s  {name[:160]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
