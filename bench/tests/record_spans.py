#!/usr/bin/env python3
"""Record the small device trace that `test_spans.py` reduces.

    python3 bench/tests/record_spans.py            # on a TPU host

Builds a small dense `ServeEngine` (2 layers, 2 heads of 128, batch 2,
128-token prompts) with the decode cell's controller (phase-aware, the
learned control round after every token), warms it up, then runs one
4-token `generate` under the JAX profiler inside the harness's
`bench.generate` span, so that the engine's own `serve.*` spans, its
named programs and the device's ops land in one trace. Writes the
profiler's `.xplane.pb` to `bench/tests/data/small_spans.xplane.pb` and
prints what the span readers make of it.
"""

from __future__ import annotations

import dataclasses
import glob
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OUT = HERE / "data" / "small_spans.xplane.pb"
NEW_TOKENS = 4


def main() -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_spans: needs a TPU", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.core.control_plane import InGraphRailController
    from repro.core.policy import POLICIES
    from repro.core.power_plane import StepProfile
    from repro.core.sor import SorConfig
    from repro.models import registry
    from repro.serve.engine import ServeEngine

    from bench import spans, trace

    cfg = dataclasses.replace(get_config("minicpm_2b", tiny=True),
                              d_model=256, n_heads=2, n_kv_heads=2)
    params = registry.build(cfg).init(jax.random.PRNGKey(0))
    profile = StepProfile(1e9, 1e9, 0.0)
    eng = ServeEngine(cfg, params, max_len=256, batch_size=2,
                      prefill_profile=profile, decode_profile=profile,
                      controller=InGraphRailController(
                          POLICIES["phase-aware"],
                          sor=SorConfig(ingest="frames")))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 128), dtype=np.int32)
    eng.generate(prompts, NEW_TOKENS)

    with tempfile.TemporaryDirectory() as tmp:
        with trace.capture(tmp):
            with jax.profiler.TraceAnnotation("bench.generate"):
                eng.generate(prompts, NEW_TOKENS)
        OUT.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace.find(tmp), OUT)

    tr = trace.load(str(OUT))
    lo, hi = trace.window(tr)
    busy = trace.busy_ns(tr, lo, hi)
    pairs = spans.enqueued_by(tr, lo, hi)
    print(f"window {(hi - lo) / 1e6:.3f} ms, busy {busy / 1e6:.3f} ms, "
          f"{len(pairs) if pairs is not None else 'unpaired'} programs")
    for name in sorted({s for _, s in pairs or []}, key=str):
        mods = {m.name.split("(")[0] for m, s in pairs if s == name}
        print(f"  enqueued by {name}: {sorted(mods)}")
    ctx = {"trace": tr, "lo": lo, "hi": hi, "busy_ns": busy,
           "window_ns": hi - lo, "work": {"kind": "serve"}}
    for names in (("serve.account", "serve.sync", "serve.control"),
                  ("serve.decode", "serve.prefill")):
        print(f"share {names}: {spans.share(ctx, 'serve', names)} "
              f"(idle alone {spans.share(ctx, 'serve', names, False)})")
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
