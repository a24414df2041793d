"""Device-idle time by the host span open over it.

A profiler trace holds the device's operations and, on the host plane, the
spans the program and the harness open (``jax.profiler.TraceAnnotation``).
``idle_by_span`` splits a device's idle time in the traced window — the
window less the union of its operations — among those spans: each piece of
idle time goes to the innermost span open over it (of the spans open, the
one that opened last), found by intersecting intervals. An idle stretch that
straddles two spans is split between them; idle time under no span goes to
``none``. Names are matched on the part before ``#``, where TraceMe writes a
span's args (``serve.prefill#rid=5#``).

Which spans take part decides what "innermost" means: with the program's
names alone (``repro.obs.SPANS``) a harness annotation nested inside a
program span does not take the time from it.
"""
from __future__ import annotations

import collections
import heapq

from bench import trace_reduce

NONE = "none"


def base_name(name: str) -> str:
    return name.split("#", 1)[0]


def innermost(spans, window) -> list[tuple[str, float, float]]:
    """The window cut into ``(name, start, end)`` pieces, each named by the
    innermost span open over it (``none`` where no span is open); two
    neighbours never share a name.
    ``spans``: ``[(name, start, end)]`` on the window's clock."""
    w0, w1 = window
    marks = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, w0), min(e, w1)
        if s < e:
            marks.append((s, 1, i))
            marks.append((e, 0, i))
    marks.sort()             # at one instant, spans close before others open
    open_, heap = set(), []  # heap: the latest start first, then the shortest
    out, t = [], w0

    def piece(name, s, e):
        if out and out[-1][0] == name:
            out[-1] = (name, out[-1][1], e)
        else:
            out.append((name, s, e))

    for x, opens, i in marks:
        if x > t:
            while heap and heap[0][2] not in open_:
                heapq.heappop(heap)
            piece(spans[heap[0][2]][0] if heap else NONE, t, x)
            t = x
        if opens:
            open_.add(i)
            heapq.heappush(heap, (-spans[i][1], spans[i][2], i))
        else:
            open_.discard(i)
    if t < w1:
        piece(NONE, t, w1)
    return out


def idle_by_span(busy, window, spans) -> dict[str, float]:
    """Idle seconds of one device in ``window`` under each innermost span.
    ``busy``: the union of its operations as sorted, disjoint ``[start,
    end]`` pairs (ns); ``spans``: ``[(name, start, end)]`` (ns)."""
    w0, w1 = window
    idle, cursor = [], w0
    for s, e in list(busy) + [[w1, w1]]:
        s, e = max(s, w0), min(e, w1)
        if s > cursor:
            idle.append([cursor, s])
        cursor = max(cursor, e)
    out = collections.Counter()
    j = 0
    for name, s, e in innermost(spans, window):   # pieces tile the window
        out[name] += 0.0
        while j < len(idle) and idle[j][1] <= s:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < e:
            out[name] += (min(e, idle[k][1]) - max(s, idle[k][0])) / 1e9
            k += 1
    return dict(out)


def named_spans(pd, names) -> list[tuple[str, float, float]]:
    """Host-plane events whose base name is in ``names``, base-named."""
    names = set(names)
    out = []
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for line in pl.lines:
            for name, s, e in trace_reduce._events(line):
                n = base_name(name)
                if n in names:
                    out.append((n, s, e))
    return out


def reduce(path, *, window: str, names, devices: int | None = None
           ) -> dict | None:
    """``idle_s``: device-idle seconds in the traced window under each
    innermost span of ``names``, mean over the first ``devices`` devices;
    ``count``: the spans of each name that open inside the window;
    ``window_s``. None when the trace holds no window or no device."""
    pd = trace_reduce.load(path)
    wins = trace_reduce.host_spans(pd, {window})
    planes = trace_reduce.device_planes(pd)[:devices]
    if not wins or not planes:
        return None
    _, w0, w1 = max(wins, key=lambda s: s[2] - s[1])
    spans = named_spans(pd, names)
    idle = collections.Counter()
    for pl in planes:
        ops = [(s, e) for ln in pl.lines if ln.name == trace_reduce.OPS_LINE
               for _, s, e in trace_reduce._events(ln)]
        busy = trace_reduce._union(ops)
        for k, v in idle_by_span(busy, (w0, w1), spans).items():
            idle[k] += v / len(planes)
    count = collections.Counter(n for n, s, _ in spans if w0 <= s < w1)
    return {"idle_s": dict(idle), "count": dict(count),
            "window_s": (w1 - w0) / 1e9}
