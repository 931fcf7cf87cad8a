#!/usr/bin/env python3
"""Record the small device trace that `test_trace.py` reduces.

    python3 bench/tests/record_trace.py            # on a TPU host

Runs, under the JAX profiler and inside the harness's own host spans, a
jitted bf16 matmul program twice, the repository's `decode_attention`
Pallas kernel once, and a 50 ms host sleep between them, then writes the
profiler's `.xplane.pb` to `bench/tests/data/small_trace.xplane.pb` and
prints, for each plane, its lines and their first events.
"""

from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OUT = HERE / "data" / "small_trace.xplane.pb"


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels import ops

    mm = jax.jit(lambda a, b: (a @ b).astype(jnp.bfloat16))
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((8, 1, 16, 64), jnp.bfloat16)
    kv = jnp.ones((8, 1024, 16, 64), jnp.bfloat16)
    lengths = jnp.full((8,), 700, jnp.int32)
    dec = jax.jit(lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                          group=1))
    mm(a, a).block_until_ready()
    dec(q, kv, kv, lengths).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.call"):
                mm(a, a).block_until_ready()
                mm(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_gap"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("bench.call"):
                dec(q, kv, kv, lengths).block_until_ready()
        (path,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        OUT.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, OUT)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(OUT))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for e in evs[:6]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, v) for k, v in list(e.stats)[:6]])
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
