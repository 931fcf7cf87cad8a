"""The profiler's device trace, and its reduction to numbers.

`capture(dir)` runs the JAX profiler around the traced window. `load(path)`
reads its `.xplane.pb` into plain lists: the device's op and program
(module) executions and the host's spans, all in nanoseconds on one clock.
The rest reduces them: busy time as the union of op intervals, a kernel's
summed time, a program's time, and the idle gaps attributed to what the
host was doing.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import re
from contextlib import contextmanager

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HARNESS_SPAN = "bench."          # the harness's own TraceAnnotation prefix
# an op's HLO name is its kernel's `pallas_call(name=...)`, perhaps behind
# the prefixes that autodiff adds and before an instance suffix ".12"
_KERNEL_NAME = re.compile(r"^(?:(?:jvp|transpose|vmap|batched)_)*(.*?)_*"
                          r"(?:\.\d+)*$")


_INSTRUCTION = re.compile(r"^%([^\s=]+)\s*=")
ENQUEUE = "DoEnqueueProgram"


def instruction(op: str) -> str:
    """An `XLA Ops` event's HLO instruction name: the event is named by the
    instruction's text, `%decode_attention.1 = bf16[...] custom-call(...)`."""
    m = _INSTRUCTION.match(op)
    return m.group(1) if m else op


def base_name(op: str) -> str:
    """`transpose_jvp_mamba2_ssd__.3` -> `mamba2_ssd`."""
    return _KERNEL_NAME.match(op).group(1)


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: dict           # device id -> [Event] of device operations
    modules: dict       # device id -> [Event] of program executions
    host: list          # [Event] of host spans, every host thread


@contextmanager
def capture(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def find(log_dir: str) -> str:
    paths = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line, instruction)
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += _events(line)
    host.sort(key=lambda e: e.start)
    return _aligned(Trace(ops, modules, host))


def _events(line, rename=lambda n: n) -> list:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(rename(e.name), start, start + int(e.duration_ns)))
    out.sort(key=lambda e: e.start)
    return out


def _aligned(tr: Trace) -> Trace:
    """The device's clock onto the host's. The device clock reads about a
    millisecond early; the n-th program execution cannot start before the
    host finished enqueuing it, and starts right after where the device
    was idle, so the least (start - enqueue end) over the pairs is the
    offset. Left as it is when the counts do not pair up."""
    if len(tr.modules) != 1:
        return tr
    (dev, mods), = tr.modules.items()
    enq = [e for e in tr.host if e.name == ENQUEUE]
    if not mods or len(enq) != len(mods):
        return tr
    off = min(m.start - q.end for m, q in zip(mods, enq))
    shift = lambda evs: [Event(e.name, e.start - off, e.end - off)
                         for e in evs]
    return Trace({d: shift(v) for d, v in tr.ops.items()},
                 {d: shift(v) for d, v in tr.modules.items()}, tr.host)


# -- reductions ---------------------------------------------------------------

def window(tr: Trace) -> tuple[int, int]:
    """From the start of the harness's first span to the end of its last."""
    spans = [e for e in tr.host if e.name.startswith(HARNESS_SPAN)]
    if not spans:
        raise RuntimeError("no harness span in the trace")
    return min(e.start for e in spans), max(e.end for e in spans)


def clip(events, lo: int, hi: int) -> list:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(intervals) -> list:
    """Merged (start, end) pairs of the given events."""
    out = []
    for e in sorted(intervals, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def busy_ns(tr: Trace, lo: int, hi: int) -> float:
    """Mean over the devices of the time some operation ran in [lo, hi]."""
    if not tr.ops:
        return 0.0
    per = [sum(b - a for a, b in union(clip(evs, lo, hi)))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def kernel_ns(tr: Trace, name: str, lo: int, hi: int) -> tuple[float, int]:
    """(summed device time, calls) of the ops named `name` in [lo, hi],
    averaged over the devices that ran it."""
    tot, calls, devs = 0, 0, 0
    for evs in tr.ops.values():
        mine = [e for e in clip(evs, lo, hi) if base_name(e.name) == name]
        if mine:
            devs += 1
            tot += sum(e.end - e.start for e in mine)
            calls += len(mine)
    return (tot / devs, calls // devs) if devs else (0.0, 0)


def programs_with(tr: Trace, kernel: str, lo: int, hi: int) -> list:
    """The program executions (of device 0) that ran an op named `kernel`,
    inside [lo, hi]."""
    dev = min(tr.modules) if tr.modules else None
    if dev is None:
        return []
    ops = [e for e in tr.ops.get(dev, []) if base_name(e.name) == kernel]
    out, i = [], 0
    for m in tr.modules[dev]:
        if m.start < lo or m.end > hi:
            continue
        while i < len(ops) and ops[i].start < m.start:
            i += 1
        if i < len(ops) and ops[i].start < m.end:
            out.append(m)
    return out


def leaves(events) -> list:
    """The events that hold no other event: a loop's op spans the ops of
    its body, which are listed too."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1].start < e.end)]


def device_ops(tr: Trace, lo: int, hi: int, top: int = 10) -> list:
    """[[op name, seconds]] of the ops (loops' bodies, not the loops) that
    took most device time."""
    dev = min(tr.ops)
    tot: dict = {}
    for e in leaves(clip(tr.ops[dev], lo, hi)):
        tot[e.name] = tot.get(e.name, 0) + (e.end - e.start)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in best]


def idle_gaps(tr: Trace, lo: int, hi: int, top: int = 10) -> list:
    """[[what the host was doing, seconds]]: the device's idle time in
    [lo, hi], summed by the innermost host span running at each gap's
    middle, joined with its enclosing harness span."""
    dev = min(tr.ops)
    busy = union(clip(tr.ops[dev], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    tot: dict = {}
    labels = host_labels(tr.host, [(a + b) // 2 for a, b in gaps])
    for (a, b), label in zip(gaps, labels):
        tot[label] = tot.get(label, 0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in best]


def host_labels(host: list, times: list) -> list:
    """What the host was doing at each of the ascending `times`: the
    innermost harness span and the innermost other span that cover it. One
    sweep over the host spans: a decode call's trace holds some 10^5 gaps
    and spans."""
    evs = sorted(host, key=lambda e: e.start)
    active, out, i = [], [], 0
    for t in times:
        while i < len(evs) and evs[i].start <= t:
            heapq.heappush(active, (evs[i].end, i))
            i += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        out.append(_label([evs[j] for _, j in active]))
    return out


def _label(covering: list) -> str:
    outer = [e for e in covering if e.name.startswith(HARNESS_SPAN)]
    inner = [e for e in covering if not e.name.startswith(HARNESS_SPAN)]
    parts = []
    if outer:
        parts.append(min(outer, key=lambda e: e.end - e.start).name)
    else:
        parts.append("outside the harness's spans")
    if inner:
        parts.append(min(inner, key=lambda e: e.end - e.start).name)
    else:
        parts.append("host python")
    return " > ".join(parts)
