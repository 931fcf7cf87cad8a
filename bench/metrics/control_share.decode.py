"""Share of the traced window that the control plane holds in decode
cells: the device time of the programs enqueued inside the engine's
accounting (`serve.account`, its pulls `serve.sync`, its control round
`serve.control`), and the device's idle time while the host was inside
them."""

from bench.spans import share


def read(ctx):
    return share(ctx, "serve",
                 ("serve.account", "serve.sync", "serve.control"))
