"""Read the serving program's own host spans out of a profiler trace.

The program opens ``jax.profiler.TraceAnnotation`` spans around its serving
phases and dispatch sites (src/repro/obs/profile.py): ``serve.*`` for the
scheduler loop, ``kv.*`` for the page arena, ``decode.*`` for the decode
programs and ``mesh.*`` for the mesh steps. They lie on the host plane of the
same XSpace as the device ops, on the same clock, and carry their counters
as event stats (``kv.paged_gather_scrub``: ``pages``, ``live_pages``). A
program without these spans yields none, and the readers then report
nothing.
"""

from __future__ import annotations

import glob
import os

from benchlib import tracing

PREFIXES = ("serve.", "kv.", "decode.", "mesh.")
# where bench/run.py --trace 1 writes its profile (removed after the readers)
TRACE_DIR = ".bench_trace"

_cache: dict = {}


def program_spans(pd, window=None) -> list:
    """``(name, start_ns, end_ns, stats)`` of every host event inside
    ``window`` (start, end ns; None = the whole trace) whose name starts
    with one of ``PREFIXES``, in order of start; ``stats`` is a dict."""
    lo, hi = window if window is not None else (float("-inf"), float("inf"))
    out = []
    for plane in pd.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not name.startswith(PREFIXES):
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if lo <= s and e <= hi:
                    out.append((name, s, e, dict(ev.stats)))
    out.sort(key=lambda sp: (sp[1], -sp[2]))
    return out


def _newest_trace(root: str):
    files = glob.glob(os.path.join(root, TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def window_spans(ctx, reader_file: str) -> list:
    """The traced window's program spans for a metric reader: the harness's
    ``ctx["spans"]`` when it provides them, else read once from the newest
    profile under the checkout's ``.bench_trace/`` (the checkout is the one
    the reader file ``reader_file`` lies in, at ``bench/metrics/``)."""
    if "spans" in ctx:
        return ctx["spans"]
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
    path = _newest_trace(root)
    if path is None:
        return []
    window = ctx["reduced"].window
    key = (path, os.path.getmtime(path), window)
    if key not in _cache:
        import jax

        _cache.clear()
        _cache[key] = program_spans(jax.profiler.ProfileData.from_file(path), window)
    return _cache[key]


def named(spans: list, name: str) -> list:
    return [sp for sp in spans if sp[0] == name]


def inside(spans: list, outer) -> list:
    """The spans that lie within ``outer``'s interval."""
    return [sp for sp in spans if outer[1] <= sp[1] and sp[2] <= outer[2]]
