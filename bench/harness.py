"""One run of one cell: set-up, the measured window, the check, the result line.

``run`` does the work for ``bench/run.py`` and for the tests, which give it
the CPU's devices and a cell of their own. The job kind of the cell's traffic
mix (``bench/jobs/<job>.py``) builds the deployment, drives the window and
compares what the window produced with the plain reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time

from bench import roofline
from bench import trace as trace_mod


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def annotate(name: str, on: bool):
    """A host span in the profiler's trace, in traced runs only."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


class CompileCounter:
    """Programs compiled (or loaded from the persistent cache), from JAX's events."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    trace: dict | None        # trace.reduce() of the traced part, or None
    work: dict                # kernel family -> (operations, bytes) in the traced part
    peak: dict
    values: dict              # the job's own per-layer readings, by name
    counters: dict            # the program's counters, summed over the window

    def idle_share(self):
        if self.trace is None or self.trace["window_ns"] <= 0:
            return None
        return 100.0 * (1.0 - self.trace["busy_ns"] / self.trace["window_ns"])

    def roofline(self, family: str, kernels) -> float | None:
        if self.trace is None or family not in self.work:
            return None
        seconds = sum(self.trace["op_ns"].get(k, 0.0) for k in kernels) / 1e9
        if seconds <= 0:
            return None
        value, bound = roofline.share(*self.work[family], seconds, self.peak)
        log(f"{family} roofline: {value} % over {seconds} s of {'/'.join(kernels)}, "
            f"bound by {bound}")
        return value


def run(cell, seed: int, seconds: float, traced: bool, devices, t_start: float,
        peak: dict, count: int | None = None) -> dict:
    """One run; ``count`` replaces the window of ``seconds`` by that many
    requests or jobs, so that the CPU tests wait on no clock."""
    counter = CompileCounter()
    job = cell.job_module().Job(cell, seed, seconds, traced, count)
    job.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s} s ({counter.count} programs compiled or loaded)")

    before = counter.count
    capture = trace_mod.Capture() if traced else None
    job.window(capture)
    log(f"{counter.count - before} programs compiled inside the window")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    job.release()

    t_ref = time.perf_counter()
    checks = job.check()
    log(f"reference comparison took {time.perf_counter() - t_ref} s")
    correct = all(value <= limit for value, limit in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": correct, "attempted": job.attempted, "failed": job.failed}
    if traced:
        t_read = time.perf_counter()
        reduced = trace_mod.reduce(capture.read())
        ctx = Context(reduced, job.work(), peak, job.layer_values(), job.counters())
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_ns"] / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        log(f"trace read and reduced in {time.perf_counter() - t_read} s")
    else:
        values = dict(job.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = device
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr, flush=True)
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, (value, limit) in checks.items()}
    return out
