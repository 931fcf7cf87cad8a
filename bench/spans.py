"""The program's own host spans in a device trace.

The serving engine and the trainer open `serve.*` and `train.*` spans
around each stage of a call or step (`jax.profiler.TraceAnnotation`, which
the profiler writes beside the device's events). Two questions are asked
of them here, from a `Trace` as `trace.load` returns it:

- which span dispatched each program execution. The n-th execute call the
  host made (`PJRT_LoadedExecutable_Execute`, on the thread that called
  the program) is paired with the n-th execution on the device, as
  `trace.load` pairs the n-th `DoEnqueueProgram` with it to align the
  clocks; the execution belongs to the innermost program span open when
  the call began. The enqueue itself may run later on a runtime thread
  (a program whose inputs are still being computed is enqueued from a
  worker), so its own time says nothing of the span that asked for it.
- which span the host was inside while the device sat idle: the gaps in
  the union of the device's op intervals, cut exactly at the boundaries
  of the innermost open program span, so that each idle nanosecond counts
  towards one span at most.
"""

from __future__ import annotations

import bisect

from bench import trace

PROGRAM_SPAN = ("serve.", "train.")
DISPATCH = "PJRT_LoadedExecutable_Execute"


def innermost(host: list) -> list:
    """[(start, end, name)]: the pieces of time in which some program span
    is open, each with the innermost one (the last opened of those still
    open), in order and disjoint."""
    spans = sorted((e for e in host if e.name.startswith(PROGRAM_SPAN)
                    and e.end > e.start), key=lambda e: (e.start, -e.end))
    # at one instant, ends before starts; an outer span before its child
    marks = sorted([(e.start, 1, i) for i, e in enumerate(spans)]
                   + [(e.end, 0, i) for i, e in enumerate(spans)])
    out, opened, t = [], [], None
    for at, starts, i in marks:
        if opened and at > t:
            out.append((t, at, spans[opened[-1]].name))
        t = at
        if starts:
            opened.append(i)
        else:
            opened.remove(i)
    return out


def enqueued_by(tr: trace.Trace, lo: int, hi: int):
    """[(program execution, span name or None)] for each device-0 program
    execution that overlaps [lo, hi]: the innermost program span open when
    the host dispatched it. None when the trace does not pair execute
    calls, enqueues and executions one to one."""
    if len(tr.modules) != 1:
        return None
    (mods,) = tr.modules.values()
    calls = [e for e in tr.host if e.name == DISPATCH]
    enq = sum(e.name == trace.ENQUEUE for e in tr.host)
    if not mods or not len(calls) == enq == len(mods):
        return None
    pieces = innermost(tr.host)
    starts = [p[0] for p in pieces]

    def at(t):
        k = bisect.bisect_right(starts, t) - 1
        return pieces[k][2] if k >= 0 and t < pieces[k][1] else None

    return [(m, at(c.start)) for m, c in zip(mods, calls)
            if m.end > lo and m.start < hi]


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two ascending lists of disjoint
    [start, end] intervals."""
    tot, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        tot += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _busy(tr: trace.Trace, lo: int, hi: int) -> list:
    return trace.union(trace.clip(tr.ops[min(tr.ops)], lo, hi))


def busy_within(tr: trace.Trace, lo: int, hi: int, names) -> int | None:
    """Device-0 busy time in [lo, hi] (the union of its op intervals)
    inside the executions of programs dispatched under one of `names`;
    None where the trace does not pair."""
    pairs = enqueued_by(tr, lo, hi)
    if pairs is None:
        return None
    mine = trace.union(trace.clip([m for m, s in pairs if s in names],
                                  lo, hi))
    return _overlap(mine, _busy(tr, lo, hi))


def idle_within(tr: trace.Trace, lo: int, hi: int, names) -> int:
    """Device-0 idle time in [lo, hi] during which the innermost open
    program span is one of `names`."""
    gaps, t = [], lo
    for a, b in _busy(tr, lo, hi):
        if a > t:
            gaps.append([t, a])
        t = max(t, b)
    if hi > t:
        gaps.append([t, hi])
    mine = [[a, b] for a, b, n in innermost(tr.host) if n in names]
    return _overlap(mine, gaps)


def share(ctx, kind: str, names, programs: bool = True):
    """100 x (device idle inside `names`, plus, with `programs`, the busy
    time of the programs they dispatched) over the traced window, for a
    cell of `kind`; None for another kind, an empty window, a program that
    opened none of `names`, or a trace that does not pair."""
    if ctx["work"]["kind"] != kind or ctx["window_ns"] <= 0:
        return None
    tr, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    if not tr.ops or not any(e.name in names for e in tr.host):
        return None
    busy = busy_within(tr, lo, hi, names)
    if busy is None:
        return None
    ns = idle_within(tr, lo, hi, names) + (busy if programs else 0)
    return 100.0 * ns / ctx["window_ns"]
