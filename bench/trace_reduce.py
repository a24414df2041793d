"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The traced window is the host span named ``window`` (the harness opens it
right after the profiler starts and closes it before it stops). On each
device plane (``/device:TPU:<n>``) the operations of the ``XLA Ops`` line are
clipped to that window, and from them come:

* ``busy_s`` — the union of the operations' intervals, and the idle share
  ``1 - busy / window``;
* ``ops`` — device seconds per operation name, each op's own time without
  the ops nested in it, and ``modules`` — seconds and count per program
  (the ``XLA Modules`` line);
* ``collective_exposed_s`` — time inside collective operations during which
  no other operation runs on that device;
* ``idle_gaps`` — the stretches of 10 us or more with no operation, each
  named by the innermost host annotation open at its middle (``none`` if
  there is none).

Numbers of several devices are given per device and as their mean.
"""
from __future__ import annotations

import collections

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 10_000   # shorter idle stretches are left unnamed
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "send", "recv")


def _union(iv):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(n.startswith(c) or f"{c}-" in n or f"{c}." in n
               for c in COLLECTIVES)


def _events(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev.name, s, s + float(ev.duration_ns)


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the op's name
    (``%paged_attention.5 = bf16[...] custom-call(...)`` -> ``paged_attention.5``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops):
    """Per name, the time of each op less that of the ops nested in it (a
    while loop holds its body's ops on the same line)."""
    out = collections.Counter()
    stack = []   # [name, end, self time]
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out[done[0]] += done[2]
        if stack:
            stack[-1][2] -= e - s
        stack.append([n, e, e - s])
    for n, _, t in stack:
        out[n] += t
    return out


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def device_planes(pd) -> list:
    out = []
    for pl in pd.planes:
        tail = pl.name.rsplit(":", 1)[-1]
        if pl.name.startswith("/device:") and tail.isdigit():
            out.append(pl)
    return sorted(out, key=lambda p: int(p.name.rsplit(":", 1)[-1]))


def host_spans(pd, names) -> list[tuple[str, float, float]]:
    spans = []
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for line in pl.lines:
            for name, s, e in _events(line):
                if name in names:
                    spans.append((name, s, e))
    return spans


def reduce_events(ops, modules, window, spans) -> dict:
    """The numbers of one device from plain event lists:
    ``ops``/``modules``: ``[(name, start_ns, end_ns)]``; ``window``:
    ``(start_ns, end_ns)``; ``spans``: host annotations as ops."""
    w0, w1 = window
    clipped = [(short_name(n),) + _clip(s, e, w0, w1) for n, s, e in ops]
    clipped = [(n, s, e) for n, s, e in clipped if s < e]
    busy = _union([(s, e) for _, s, e in clipped])
    per_op = self_times(clipped)
    coll = _union([(s, e) for n, s, e in clipped if is_collective(n)])
    other = _union([(s, e) for n, s, e in clipped if not is_collective(n)])
    exposed = _length(coll) - _length(_intersect(coll, other))
    mods = collections.defaultdict(lambda: [0.0, 0])
    for n, s, e in modules:
        cs, ce = _clip(s, e, w0, w1)
        if cs < ce:
            mods[n][0] += (ce - cs) / 1e9
            mods[n][1] += 1
    gaps = []
    cursor = w0
    for s, e in busy + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = []
    for s, e in gaps:
        if e - s < MIN_GAP_NS:
            continue
        mid = (s + e) / 2
        open_ = [(ss, n) for n, ss, ee in spans if ss <= mid <= ee]
        named.append([max(open_)[1] if open_ else "none", (e - s) / 1e9])
    named.sort(key=lambda g: -g[1])
    window_s = (w1 - w0) / 1e9
    busy_s = _length(busy) / 1e9
    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "ops": {n: t / 1e9 for n, t in per_op.items()},
        "modules": {n: tuple(v) for n, v in mods.items()},
        "collective_s": _length(coll) / 1e9,
        "collective_exposed_s": exposed / 1e9,
        "idle_gaps": named,
    }


def reduce(path, *, window: str, annotations, devices: int | None = None
           ) -> dict | None:
    """Per-device numbers and their means over the first ``devices``
    device planes; None when the trace holds no window or no device."""
    pd = load(path)
    wins = host_spans(pd, {window})
    planes = device_planes(pd)[:devices]
    if not wins or not planes:
        return None
    _, w0, w1 = max(wins, key=lambda s: s[2] - s[1])
    spans = host_spans(pd, set(annotations))
    per = []
    for pl in planes:
        lines = {ln.name: ln for ln in pl.lines}
        ops = list(_events(lines[OPS_LINE])) if OPS_LINE in lines else []
        mods = (list(_events(lines[MODULES_LINE]))
                if MODULES_LINE in lines else [])
        per.append(reduce_events(ops, mods, (w0, w1), spans))
    return combine(per, [pl.name for pl in planes])


def combine(per: list[dict], names: list[str]) -> dict:
    n = len(per)
    mean = lambda k: sum(p[k] for p in per) / n
    ops = collections.Counter()
    for p in per:
        for k, v in p["ops"].items():
            ops[k] += v / n
    gaps = sorted((g for p in per for g in p["idle_gaps"]),
                  key=lambda g: -g[1])
    return {
        "devices": dict(zip(names, per)),
        "window_s": mean("window_s"), "busy_s": mean("busy_s"),
        "idle_share": 1.0 - mean("busy_s") / mean("window_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "device_ops": [[k, v] for k, v in ops.most_common()],
        "idle_gaps": gaps,
    }


def describe(path, top: int = 25) -> str:
    """A plain listing of a trace: planes, lines, event counts and the
    names that take most time, with one event's stats per line."""
    pd = load(path)
    out = []
    for pl in pd.planes:
        out.append(f"PLANE {pl.name}")
        for ln in pl.lines:
            evs = list(_events(ln))
            tot = collections.Counter()
            for nme, s, e in evs:
                tot[nme] += e - s
            out.append(f"  LINE {ln.name!r}: {len(evs)} events")
            for nme, t in tot.most_common(top):
                out.append(f"    {t / 1e6:12.3f} ms  {nme[:160]}")
            for ev in list(ln.events)[:1]:
                out.append(f"    stats: {[(k, str(v)[:120]) for k, v in ev.stats]}")
    return "\n".join(out)
