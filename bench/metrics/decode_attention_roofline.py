"""The `decode_attention` kernel's share of its roofline over its summed
device time; work at the published 36 heads and the rows' lengths."""

from bench import work
from bench.readers import decode_steps, share
from bench.trace import kernel_ns


def read(ctx):
    w, pk = ctx["work"], ctx["peaks"]
    if w["kind"] != "serve" or w["new_tokens"] < 2:
        return None
    ns, calls = kernel_ns(ctx["trace"], "decode_attention", ctx["lo"],
                          ctx["hi"])
    steps = decode_steps(w)
    per_step = w["shapes"]["n_layers"]
    if calls == 0 or calls % (per_step * len(steps)):
        return None
    roof = 0.0
    for c in steps:
        f, b = work.decode_attention_call(w["shapes"], w["batch"], c)
        roof += per_step * max(f / pk["bf16_flops_per_s"],
                               b / pk["hbm_bytes_per_s"])
    return share(roof * calls / (per_step * len(steps)), ns)
