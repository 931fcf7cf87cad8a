"""What the drivers share: the program's model configuration from a bench
configuration, seeded host generators, the host clock and host spans."""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


def program_config(config: dict):
    """The program's ModelConfig for a bench configuration: the program's
    own CONFIG of `program.arch`, with `program.overrides` applied (the
    depth cut, or a small size in the tests)."""
    from repro.configs import get_config
    prog = config["program"]
    return dataclasses.replace(get_config(prog["arch"]),
                               **prog.get("overrides", {}))


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A host generator for one stream of inputs drawn from the seed."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


class Clock:
    """Host clock from a fixed origin."""

    def __init__(self, t0: float | None = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def now(self) -> float:
        return time.perf_counter() - self.t0


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)
