"""The `mla_decode_attention` kernel's share of its roofline over its
summed device time: per call, one layer's rows read their latent cache (576
bf16 values a position) and do 2 x batch x 16 heads x context x (576 + 512)
flops, at the rows' lengths (bench/work_mla.py)."""

from bench import work_mla
from bench.readers import decode_steps, share
from bench.trace import kernel_ns


def read(ctx):
    w, pk = ctx["work"], ctx["peaks"]
    if w["kind"] != "serve" or w["new_tokens"] < 2:
        return None
    ns, calls = kernel_ns(ctx["trace"], "mla_decode_attention", ctx["lo"],
                          ctx["hi"])
    steps = decode_steps(w)
    per_step = w["shapes"]["n_layers"]
    if calls == 0 or calls % (per_step * len(steps)):
        return None
    roof = 0.0
    for c in steps:
        f, b = work_mla.mla_decode_call(w["shapes"], w["batch"], c)
        roof += per_step * max(f / pk["bf16_flops_per_s"],
                               b / pk["hbm_bytes_per_s"])
    return share(roof * calls / (per_step * len(steps)), ns)
