"""Share of the traced window in which the device sat idle while the
serving engine's host was enqueueing a model program (`serve.decode`,
`serve.prefill`), the allocation of the step's outputs included (decode
cells)."""

from bench.spans import share


def read(ctx):
    return share(ctx, "serve", ("serve.decode", "serve.prefill"),
                 programs=False)
