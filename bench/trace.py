"""Profiler capture of the traced window, and its reduction to numbers.

``Capture`` wraps ``jax.profiler`` around part of the window and marks that
part with the host annotation ``bench.window``. ``extract`` turns the
``.xplane.pb`` it writes into plain lists (device ops per chip, host events per
thread), which is also the form of the recorded trace the tests read.
``reduce`` computes, inside the window:

- busy time: the union of the intervals in which an op ran, per chip;
- the device time of each op, by its short name (``%support_count_pallas.1 =
  ...`` is ``support_count_pallas``);
- the longest idle gaps, each named by the bench annotation around it and the
  host event that overlaps it most.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench.window"
_SHORT = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")


def short_name(op: str) -> str:
    m = _SHORT.match(op)
    return m.group(1) if m else op.split(" ", 1)[0]


class Capture:
    """Start and stop the profiler around the traced part of a window."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._ann = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()

    def stop(self):
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self) -> dict:
        path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)[0]
        events = extract(path)
        shutil.rmtree(self.dir, ignore_errors=True)
        return events


def extract(path: str) -> dict:
    """{"devices": {plane: [[op, start_ns, dur_ns], ...]},
        "host": [[thread, name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:CPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["devices"][plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                                  for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"].extend([line.name, e.name, e.start_ns, e.duration_ns]
                                   for e in line.events)
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: dict, top: int = 10) -> dict:
    win = [(s, s + d) for t, n, s, d in events["host"] if n == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW} annotation")
    w0, w1 = win[0]
    busy, kernels, gaps = [], {}, []
    for ops in events["devices"].values():
        spans = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                spans.append((a, b))
                key = short_name(name)
                kernels[key] = kernels.get(key, 0.0) + (b - a)
        merged = _union(spans)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_ns": w1 - w0,
        "busy_ns": sum(busy) / max(len(busy), 1),
        "chips": len(busy),
        "op_ns": kernels,
        "device_ops": sorted(([k, v / 1e9] for k, v in kernels.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_blame(events["host"], a, b), (b - a) / 1e9] for a, b in gaps],
    }


def _blame(host, a, b) -> str:
    """The innermost bench annotation around a gap, and the other host event
    that overlaps it most."""
    mid, ann, best = (a + b) / 2, None, (0, "idle")
    for thread, name, s, d in host:
        if name == WINDOW:
            continue
        if name.startswith("bench.") and s <= mid <= s + d:
            if ann is None or d < ann[1]:
                ann = (name, d)
        elif not name.startswith("bench."):
            over = min(b, s + d) - max(a, s)
            if over > best[0]:
                best = (over, name)
    where = ann[0][len("bench."):] if ann else "window"
    return f"{where} / {best[1]}"
