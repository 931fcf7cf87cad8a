"""Share of the traced window in which no operation ran on the device
(train cells)."""

from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx, "train")
