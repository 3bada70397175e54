"""One run of one cell: set-up, the measured window, the traced segment,
the outputs check, and the result line.

``run_cell`` is everything after the entry point's look for a chip, so a
test can drive a whole run on the CPU at a small size.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from . import cells, e2e, gen, reference, trace
from .client import Client


class CompileCounter:
    """Counts the executables JAX compiles or loads while armed."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.requests = 0
        self.backend = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if self.armed and event in self.EVENTS:
            self.requests += 1

    def _duration(self, event, duration, **kw):
        if self.armed and event in self.DURATIONS:
            self.backend += 1

    @property
    def compiles(self) -> int:
        return max(self.requests, self.backend)


def warmup(srv, stream: gen.RequestStream) -> Dict:
    """Run each (op, bucket) the configuration's requests land in once, at
    the padded batch the window uses, and nothing else."""
    t0 = time.monotonic()
    seen = {}
    for i, s in enumerate(stream.shapes):
        seen.setdefault((s.op, srv.policy.bucket_shape(s.shape)), i)
    for (op, _), i in sorted(seen.items()):
        srv.submit(stream.bases[i], op=op)
        srv.drain()
    srv.stats.reset()
    return {"executables": len(seen), "seconds": time.monotonic() - t0}


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats:
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def window_records(srv, t_start: float) -> Dict:
    """The server's own records of the window's flushes and requests."""
    return {"flushes": [f for f in srv.stats.flush_records
                        if f.t_dispatch >= t_start],
            "requests": [r for r in srv.stats.records
                         if r.t_dispatch >= t_start]}


def traced_segment(srv, stream, loop, traffic, seed, devices
                   ) -> Optional[trace.TraceSummary]:
    """Run the loop again for ``trace_seconds`` under the profiler and
    reduce the trace; the measured window is over before this starts."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    client = Client(srv, stream, prefetch=int(traffic.get("prefetch", 0)))
    client.prime(int(traffic.get("outstanding", 0)))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                loop.run(client, traffic, float(traffic["trace_seconds"]),
                         seed)
        finally:
            jax.profiler.stop_trace()
            client.close()
        return trace.summarize(trace.xplane_path(tdir),
                               [d.id for d in devices])


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool,
             t_process: float, devices) -> Dict:
    """One run; returns the result line's object."""
    from repro.serving import PCAServer, ServerSpec

    counter = CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    stream = gen.RequestStream(cfg["requests"], seed)
    spec = ServerSpec.from_json(json.dumps(cfg["server_spec"]))
    srv = PCAServer.from_spec(spec)
    warm = warmup(srv, stream)
    loop = cells.load_module("loops", traffic["loop"])
    client = Client(srv, stream, prefetch=int(traffic.get("prefetch", 0)))
    client.prime(int(traffic.get("outstanding", 0)))
    counter.armed = True
    try:
        t_end = loop.run(client, traffic, seconds, seed)
    finally:
        counter.armed = False
        client.close()
    window = e2e.Window(client.t_start, t_end, client.sent)
    setup_s = client.t_start - t_process
    recs = window_records(srv, client.t_start)
    misses = sum(1 for r in recs["requests"] if not r.cache_hit)
    late = [s.t_submit - s.due for s in window.sent]
    say(f"client: {len(window.sent)} requests in {window.seconds:.3f} s "
        f"window; submitted late by p50 {np.percentile(late, 50)*1e3:.3f} "
        f"ms, p99 {np.percentile(late, 99)*1e3:.3f} ms, max "
        f"{max(late)*1e3:.3f} ms")
    say(f"compiles in window: {counter.compiles} (jax), {misses} "
        f"(server cache misses); warm-up built {warm['executables']} "
        f"executables in {warm['seconds']:.3f} s; cache "
        f"{json.dumps(srv.cache_summary())}")

    summary, traced_recs = None, None
    if traced:
        srv.stats.reset()
        summary = traced_segment(srv, stream, loop, traffic, seed,
                                 devices[:cell.chips])
        traced_recs = {"flushes": list(srv.stats.flush_records),
                       "requests": list(srv.stats.records)}
    memory = peak_memory(devices[:cell.chips])
    del srv

    ctx = {"window": window, "records": recs, "trace": summary,
           "traced_records": traced_recs,
           "cell": cell, "devices": devices[:cell.chips],
           "setup_s": setup_s, "compiles": counter.compiles + misses}
    if traced:
        metrics = {}
        for m in cell.per_layer:
            family = m["name"].split(".")[0]
            value = cells.load_module("layers", family).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = (setup_s if m["name"] == "setup_s"
                     else e2e.METRICS[m["name"]](window))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    readings, failed = check(stream, window, cfg["check"], seed)
    limits = cfg["check"]["limits"]
    checks = {name: {"value": readings.get(name, 0.0), "limit": float(lim)}
              for name, lim in limits.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    say("readings not compared: " + ", ".join(
        f"{k} {v!r}" for k, v in sorted(readings.items()) if k not in limits))
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    d0 = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": len(window.sent),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": cell.chips, "memory_peak_bytes": memory},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        top = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[name[:120], sec] for name, sec in top],
            "idle_gaps": [list(g) for g in summary.gaps[:10]]}
    result["checks"] = checks
    return result


def check(stream, window: e2e.Window, spec: Dict, seed: int):
    """The readings of every number (worst and mean) over a seeded sample
    of the window's answers (every one when the window holds fewer than
    ``sample``), against the float64 reference; and how many requests
    went unanswered."""
    done = [s for s in window.sent if s.ticket.done]
    failed = len(window.sent) - len(done)
    n = int(spec.get("sample", len(done)))
    if len(done) > n:
        rng = gen.rng_for(seed, gen.SAMPLE)
        idx = rng.choice(len(done), size=n, replace=False)
        # the largest requests always belong to the sample
        big = max(range(len(done)), key=lambda i: done[i].req.matrix.size)
        done = [done[i] for i in sorted(set(idx.tolist()) | {big})]
    pairs = [(s.req, s.ticket.result()) for s in done]
    return reference.readings(stream, pairs), failed
