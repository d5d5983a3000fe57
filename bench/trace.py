"""The profiler trace of a window, and its reduction to device numbers.

``load`` turns an ``.xplane.pb`` into plain event tuples
``(plane, line, name, start_ns, end_ns)``; ``reduce`` works on those
alone, so a test can feed it a small recorded trace.

* busy: the union of the intervals of chip 0's ``XLA Ops`` inside the
  host span ``bench.window``;
* device ops: the ops that took the most device time in the window;
* idle gaps: each stretch of the window with no op on chip 0, named by
  the innermost host event that covers its middle on the thread that
  ran the window (``bench.step``, ``bench.submit``, ``bench.count`` or
  a jax event inside them), summed by name; gaps under 10 us between
  ops are summed apart.
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10
NAME_CHARS = 160      # an op's name is its HLO text, cut to this length
MIN_GAP_NS = 10_000   # shorter gaps lie between ops, not under host work
SHORT_GAP = "(between ops, under 10 us)"


def start() -> str:
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def cleanup(d: str) -> None:
    shutil.rmtree(d, ignore_errors=True)


def load(d: str) -> list[tuple]:
    """Events of the device planes and the host plane of the trace in
    directory ``d``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {d}")
    out = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for pl in pd.planes:
            if not (pl.name.startswith("/device:") or pl.name == HOST_PLANE):
                continue
            for ln in pl.lines:
                for e in ln.events:
                    out.append((pl.name, ln.name, e.name,
                                float(e.start_ns), float(e.end_ns)))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: list[tuple], device_plane: str = DEVICE_PLANE) -> dict:
    """busy_s, window_s, device_ops and idle_gaps of the traced window."""
    win = [e for e in events if e[0] == HOST_PLANE and e[2] == WINDOW]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found {len(win)}")
    _, host_line, _, w0, w1 = win[0]
    ops = [(max(s, w0), min(e, w1), name)
           for pl, ln, name, s, e in events
           if pl == device_plane and ln == OPS_LINE and e > w0 and s < w1]
    if not ops:
        raise RuntimeError(f"no {OPS_LINE!r} events of {device_plane} in "
                           "the window")
    busy = _union((s, e) for s, e, _ in ops)
    per_op: dict[str, float] = {}
    for s, e, name in ops:
        per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
    host = sorted((s, e, name) for pl, ln, name, s, e in events
                  if pl == HOST_PLANE and ln == host_line and name != WINDOW)
    starts = [h[0] for h in host]
    gaps: dict[str, float] = {}
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        name = SHORT_GAP
        if g1 - g0 >= MIN_GAP_NS:
            # innermost host event covering the gap's middle: the latest
            # one to start before it that has not yet ended
            mid = 0.5 * (g0 + g1)
            k = bisect.bisect_right(starts, mid) - 1
            while k >= 0 and host[k][1] < mid:
                k -= 1
            name = host[k][2] if k >= 0 else "(no host span)"
        gaps[name] = gaps.get(name, 0.0) + (g1 - g0) * 1e-9
    busy_ns = sum(e - s for s, e in busy)

    def top(d):
        return [[k[:NAME_CHARS], v]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:TOP]

    return {"busy_s": busy_ns * 1e-9, "window_s": (w1 - w0) * 1e-9,
            "device_ops": top(per_op), "idle_gaps": top(gaps)}
