"""The `mamba2_ssd` kernel's share of its roofline (the SSD recurrence of
one layer over the step's tokens) over its summed device time."""

from bench import work
from bench.readers import share
from bench.trace import kernel_ns


def read(ctx):
    w, pk = ctx["work"], ctx["peaks"]
    if w["kind"] != "train":
        return None
    ns, calls = kernel_ns(ctx["trace"], "mamba2_ssd", ctx["lo"], ctx["hi"])
    f, b = work.mamba2_ssd_call(w["shapes"], w["batch"], w["seq"])
    roof = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return share(calls * roof, ns)
