"""The train step's model flops (forward and backward of the published
shapes, nothing recomputed) over its device time and the bf16 peak."""

from bench import work
from bench.readers import program_ns, share


def read(ctx):
    w = ctx["work"]
    if w["kind"] != "train":
        return None
    ns, n = program_ns(ctx, "mamba2_ssd")
    f = work.hybrid_train_step(w["shapes"], w["batch"], w["seq"])
    return share(n * f / ctx["peaks"]["bf16_flops_per_s"], ns)
