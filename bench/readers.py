"""Reductions that the per-layer metric readers (bench/metrics/*.py) share.
Each takes the reader's context: the loaded trace, the traced window
[lo, hi] in ns, the device's busy time in it, what the driver counted of
the traced work (`work`) and the chip's peaks. A reader that finds nothing
to read returns None."""

from __future__ import annotations

from bench import trace, work


def idle_share(ctx, kind: str):
    if ctx["work"]["kind"] != kind or ctx["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_ns"] / ctx["window_ns"])


def program_ns(ctx, kernel: str) -> tuple[float, int]:
    """(device time, executions) of the programs that ran `kernel`."""
    progs = trace.programs_with(ctx["trace"], kernel, ctx["lo"], ctx["hi"])
    return float(sum(p.end - p.start for p in progs)), len(progs)


def decode_steps(w) -> list:
    """Context length of each decode step of one generate call: the i-th
    step's token attends to prompt_len + i + 1 positions."""
    return [w["prompt_len"] + i + 1 for i in range(w["new_tokens"] - 1)]


def mean_decode_step(ctx) -> tuple[float, float, float]:
    """(flops, bytes, roofline seconds) of a decode step, averaged over the
    steps of a call."""
    w, pk = ctx["work"], ctx["peaks"]
    f = b = r = 0.0
    steps = decode_steps(w)
    for c in steps:
        fi, bi = work.dense_decode_step(w["shapes"], w["batch"], c)
        f, b = f + fi, b + bi
        r += max(fi / pk["bf16_flops_per_s"], bi / pk["hbm_bytes_per_s"])
    n = len(steps)
    return f / n, b / n, r / n


def share(seconds_at_peak: float, ns: float):
    if ns <= 0 or seconds_at_peak <= 0:
        return None
    return 100.0 * seconds_at_peak / (ns / 1e9)
