"""From a profiler trace (``.xplane.pb``) to busy time, per-op totals and
idle gaps.  Benchmark code: every PR computes these the same way.

Only ``jax.profiler.ProfileData`` is needed to read the file.  A device
plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds the
operations, nested in time where one (a ``while``) runs others.  Busy time is
the union of that line's intervals, clipped to the window; the window is the
span from the first to the last ``bench.request`` annotation the harness wrote
into the trace, one around each traced cycle, so starting and stopping the
profiler is not counted as idle.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINES = ("XLA Ops", "XLA Modules")
# one annotation a traced cycle; the recorded fixture carries this name
ANNOTATION = "bench.request"


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """The trace prints a device op as its whole HLO instruction
    (``%while.167 = (u32[], ...) while(...)``): keep the instruction's name,
    and for a custom call its target (``custom-call.3:tpu_custom_call`` is a
    Mosaic kernel)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = _TARGET.search(text)
    return f"{name}:{target.group(1)}" if target else name


def load(path: str):
    """The trace as plain lists: ``[(plane, [(line, [(name, start_ns,
    dur_ns)])])]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name, [
            (line.name, [(op_name(e.name), float(e.start_ns), float(e.duration_ns))
                         for e in line.events])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


def _nest(events):
    """``[[name, start, end, depth, self_ns]]`` of one line's events."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[4] -= min(end, parent[2]) - start
        out.append([name, start, end, len(stack), dur])
        stack.append(len(out) - 1)
    return out


def reduce(planes, window=None) -> dict:
    """Busy seconds (mean over device planes), window seconds, per-op totals
    and the idle gaps of the busiest device.

    ``top``: seconds by name of the ops no other op contains.  ``any``:
    seconds by name of every op, children counted inside their parents too.
    ``self``: seconds by name with each op's children taken out; sums to
    busy.  ``gaps``: ``[(start_ns, dur_ns)]``, longest first.
    """
    annotations = sorted(
        (start, start + dur)
        for pname, lines in planes if pname.startswith("/host:")
        for _, events in lines
        for name, start, dur in events if name == ANNOTATION
    )
    devices = {}
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        by_name = dict(lines)
        line = next((n for n in OPS_LINES if by_name.get(n)), None)
        if line:
            devices[pname] = by_name[line]
    lines_seen = [[pname, [[ln, len(ev)] for ln, ev in lines]]
                  for pname, lines in planes if not pname.startswith("/host:")]
    empty = {"lines": lines_seen, "devices": 0, "busy_s": 0.0, "window_s": 0.0,
             "top": {}, "any": {},
             "self": {}, "gaps": [], "annotations": annotations}
    if not devices:
        return empty
    if window is None:
        if annotations:
            window = (annotations[0][0], annotations[-1][1])
        else:
            every = [e for ev in devices.values() for e in ev]
            window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    busy, top, anyl, selfl, gaps = [], {}, {}, {}, []
    for _, events in sorted(devices.items()):
        covered, cursor, dev_gaps = 0.0, window[0], []
        for name, start, end, depth, self_ns in _nest(events):
            a, b = max(start, window[0]), min(end, window[1])
            if b <= a:
                continue
            share = (b - a) / (end - start)
            anyl[name] = anyl.get(name, 0.0) + (b - a) / 1e9
            selfl[name] = selfl.get(name, 0.0) + self_ns * share / 1e9
            if depth == 0:
                top[name] = top.get(name, 0.0) + (b - a) / 1e9
                if a > cursor:
                    dev_gaps.append((cursor, a - cursor))
                if b > cursor:
                    covered += b - max(a, cursor)
                    cursor = b
        if window[1] > cursor:
            dev_gaps.append((cursor, window[1] - cursor))
        busy.append(covered / 1e9)
        if busy[-1] == max(busy):
            gaps = dev_gaps
    k = len(busy)
    return {
        "lines": lines_seen,
        "devices": k,
        "busy_s": sum(busy) / k,
        "window_s": (window[1] - window[0]) / 1e9,
        "top": {n: v / k for n, v in top.items()},
        "any": {n: v / k for n, v in anyl.items()},
        "self": {n: v / k for n, v in selfl.items()},
        "gaps": sorted(gaps, key=lambda g: -g[1]),
        "annotations": annotations,
    }


def name_gaps(gaps, spans, offset_s: float, annotations, limit: int = 10):
    """``[[what the host was doing, seconds]]`` for the longest gaps.

    ``spans`` are the program's (``start_s`` on the wall clock, ``dur_ms``);
    ``offset_s`` is wall time minus trace time.  A gap is named by the
    shortest program span that covers its midpoint; inside a cycle but outside
    every span it is the client's or the wire's time, outside every cycle the
    harness's.
    """
    timed = [((sp["start_s"] - offset_s) * 1e9, sp["dur_ms"] * 1e6, sp["name"])
             for sp in spans]
    out = []
    for start, dur in gaps[:limit]:
        mid = start + dur / 2
        cover = [(d, n) for s, d, n in timed if s <= mid <= s + d]
        if cover:
            name = min(cover)[1]
        elif any(a <= mid <= b for a, b in annotations):
            name = "client or wire: in a cycle, outside the server's spans"
        else:
            name = "harness: between cycles"
        out.append([name, dur / 1e9])
    return out
