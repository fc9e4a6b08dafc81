"""Any capture by the program's own names: device time by scope, host spans, idle gaps by span.

``python3 -m perfbench.tools.spans <file.xplane.pb | trace dir> [scopes dir | scope map .json] [gradient steps a block]``

The capture is one an ``obs.capture_steps`` run left under ``<log_dir>/xprof`` (with
``obs.host_tracer_level=1`` or higher, or it holds no span) or a perfbench run under
``.perfbench/trace/<cell>``; the scope maps are ``<log_dir>/scopes/**/*.json``.  Without the
second argument they are looked for in the two places a run leaves them: ``<log_dir>/scopes``
beside the capture's ``xprof/``, and ``.perfbench/logs/<cell>`` for ``.perfbench/trace/<cell>``;
nowhere else, and with none the device's time is not grouped.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[2]


def scopes_of(capture: Path) -> Optional[Path]:
    """Where the run that made ``<trace dir>/plugins/profile/<stamp>/<host>.xplane.pb`` left its scope maps."""
    if len(capture.resolve().parents) < 5:
        return None
    trace_dir = capture.resolve().parents[3]
    for found in (trace_dir.parent / "scopes", trace_dir.parent.parent / "logs" / trace_dir.name if trace_dir.parent.name == "trace" else None):
        if found is not None and found.is_dir():
            return found
    return None


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from perfbench.readers import spans, xplane

    path = Path(argv[0])
    capture = path if path.is_file() else xplane.find_xplane(path)
    scopes = Path(argv[1]) if len(argv) > 1 else scopes_of(capture)
    steps = float(argv[2]) if len(argv) > 2 else 1.0
    maps = spans.load_scope_maps(scopes)
    print(f"capture {capture}; scope maps for modules {sorted(maps)} from {scopes}" + ("" if maps else ": the device's time is not grouped (name the scopes directory)"))
    for line in spans.tables(spans.reduce_capture(xplane.load(capture), maps), steps):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
