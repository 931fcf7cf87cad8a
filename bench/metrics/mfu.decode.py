"""The decode program's model flops (published shapes) over its device time
and the chip's bf16 peak."""

from bench.readers import mean_decode_step, program_ns, share


def read(ctx):
    if ctx["work"]["kind"] != "serve" or ctx["work"]["new_tokens"] < 2:
        return None
    ns, n = program_ns(ctx, "decode_attention")
    flops, _, _ = mean_decode_step(ctx)
    return share(n * flops / ctx["peaks"]["bf16_flops_per_s"], ns)
