"""Share of the traced window that the control plane holds outside the
train step program: the device time of the programs enqueued inside the
trainer's telemetry pull (`train.telemetry`) and host-path control round
(`train.control`), and the device's idle time while the host was inside
them. The control round compiled into the step is not separable: the
trace names no op scope (training cells)."""

from bench.spans import share


def read(ctx):
    return share(ctx, "train", ("train.telemetry", "train.control"))
