"""The decode program's share of its roofline: the larger of its model
flops over the bf16 peak and its bytes (weights once, K/V at the rows'
lengths, published shapes) over HBM bandwidth, over its device time."""

from bench.readers import mean_decode_step, program_ns, share


def read(ctx):
    if ctx["work"]["kind"] != "serve" or ctx["work"]["new_tokens"] < 2:
        return None
    ns, n = program_ns(ctx, "decode_attention")
    _, _, roof = mean_decode_step(ctx)
    return share(n * roof, ns)
