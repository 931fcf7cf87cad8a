"""The decode program's share of its roofline in a DeepSeek-V3-block cell:
the larger of its model flops over the bf16 peak and its bytes over HBM
bandwidth, averaged over the steps of a call, over the device time of the
programs that ran `mla_decode_attention` (the decode steps; prefill does
not run it). Bytes: every weight a step reads whatever the routing, the
held experts the window's steps hit on average (the engine's
`moe_experts_hit`), the latent cache at the rows' lengths; flops count the
window's mean (token, held expert) pairs (`moe_pairs_held`)
(bench/work_mla.py)."""

from bench import work_mla
from bench.readers import decode_steps, program_ns, share


def read(ctx):
    w, pk = ctx["work"], ctx["peaks"]
    if (w["kind"] != "serve" or w["new_tokens"] < 2
            or w.get("moe_experts_hit") is None):
        return None
    ns, n = program_ns(ctx, "mla_decode_attention")
    steps = decode_steps(w)
    roof = 0.0
    for c in steps:
        f, b = work_mla.decode_step(w["shapes"], w["batch"], c,
                                    w["moe_experts_hit"],
                                    w["moe_pairs_held"])
        roof += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return share(n * roof / len(steps), ns)
