"""Serving driver: a closed loop of back-to-back `ServeEngine.generate`
calls, each a batch of seeded prompts of one length.

Traffic parameters: batch, prompt_len, new_tokens, policy, control_path,
sor (the learned control round after every token), trace_seconds (the
traced window), check_rows (requests whose served tokens the reference
scores once the window has closed), ref_rows (requests the reference runs
at once, so that its attention fits).
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.drivers.common import program_config, rng, span
from bench.reference import dense as dense_ref


def program_params(cfg, shapes: dict, seed: int):
    """The bench's dense weights laid out as the program stores them: query
    and K/V heads placed into the program's padded head slots (pad slots
    zero), vocabulary rows padded with zeros."""
    plan = cfg.head_plan()
    q_src, kv_src = np.asarray(plan.q_src), np.asarray(plan.kv_src)
    Vp = cfg.vocab_padded

    def heads(a, src, axis):
        a = jnp.take(a, np.maximum(src, 0), axis=axis)
        shape = [1] * a.ndim
        shape[axis] = len(src)
        return a * jnp.asarray(src >= 0, a.dtype).reshape(shape)

    @functools.partial(jax.jit, donate_argnums=0)
    def layout(w):
        lay, V = w["layers"], w["embed"].shape[0]
        return {
            "embed": jnp.pad(w["embed"], ((0, Vp - V), (0, 0))),
            "final_norm_w": w["final_norm"],
            "lm_head": jnp.pad(w["lm_head"], ((0, 0), (0, Vp - V))),
            "blocks": {
                "ln1_w": lay["ln1"],
                "attn": {"wq": heads(lay["wq"], q_src, 2),
                         "wk": heads(lay["wk"], kv_src, 2),
                         "wv": heads(lay["wv"], kv_src, 2),
                         "wo": heads(lay["wo"], q_src, 1)},
                "ln2_w": lay["ln2"],
                "mlp": {"w_gate": lay["w_gate"], "w_in": lay["w_up"],
                        "w_out": lay["w_down"]},
            },
        }

    # the same compiled program makes the weights here and for the
    # reference, so both start from the same bits
    return layout(weights.make("dense", shapes, seed))


def build_engine(cfg, traffic: dict, params):
    """A ServeEngine as `repro.launch.serve.build_engine` makes it, over the
    given weights."""
    from repro.core.control_plane import (HostRailController,
                                          InGraphRailController)
    from repro.core.policy import POLICIES
    from repro.core.power_plane import StepProfile
    from repro.core.sor import SorConfig
    from repro.launch.serve import CACHE_ALIGN
    from repro.serve.engine import ServeEngine

    B, Tp, new = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    policy = POLICIES[traffic["policy"]]
    if traffic["control_path"] == "in-graph":
        sor = SorConfig(ingest="frames") if traffic["sor"] else None
        controller = InGraphRailController(policy, sor=sor)
    else:
        controller = HostRailController(policy, n_chips=1)
    max_len = -(-(Tp + new) // CACHE_ALIGN) * CACHE_ALIGN
    return ServeEngine(
        cfg, params, max_len=max_len, batch_size=B,
        prefill_profile=StepProfile(2.0 * n * B * Tp, 2.0 * n, 0.0),
        decode_profile=StepProfile(2.0 * n * B, 2.0 * n, 0.0),
        controller=controller)


class Serve:
    def __init__(self, cell: dict, seed: int):
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed = seed
        self.shapes = self.config["shapes"]
        self.cfg = program_config(self.config)
        t = self.traffic
        self.batch, self.prompt_len, self.new = (t["batch"], t["prompt_len"],
                                                 t["new_tokens"])
        self.calls = []          # (prompts, served tokens, start, end)

    def prompts(self, j: int) -> np.ndarray:
        return rng(self.seed, 1, j).integers(
            0, self.shapes["vocab"], (self.batch, self.prompt_len),
            dtype=np.int32)

    # -- set-up: weights on the device, every program compiled -----------
    def setup(self) -> None:
        params = program_params(self.cfg, self.shapes, self.seed)
        self.engine = build_engine(self.cfg, self.traffic, params)
        # two tokens compile prefill, the decode step, the control round
        # and the eager argmax and index ops; the host-side concatenation
        # of all new tokens is compiled at its own arity
        out = self.engine.generate(self.prompts(0), min(2, self.new))
        jnp.concatenate([jnp.asarray(out[:, :1])] * self.new, axis=1)

    # -- the measured window ----------------------------------------------
    def window(self, seconds: float, clock) -> float:
        t0 = clock.now()
        j = 1
        while True:
            p = self.prompts(j)
            s = clock.now()
            with span("bench.generate"):
                out = self.engine.generate(p, self.new)
            e = clock.now()
            self.calls.append((p, out, s, e))
            j += 1
            if e - t0 >= seconds:
                return e - t0

    def durations(self) -> list:
        """Host seconds of each call of the window."""
        return [x[-1] - x[-2] for x in self.calls]

    def attempted(self) -> int:
        return len(self.calls) * self.batch

    def failed(self) -> int:
        bad = 0
        for _, out, _, _ in self.calls:
            ok = (out.shape == (self.batch, self.new)) & \
                np.all((out >= 0) & (out < self.shapes["vocab"]), axis=1)
            bad += int(np.sum(~ok))
        return bad

    def end_to_end(self, elapsed: float) -> dict:
        tokens = sum(out.size for _, out, _, _ in self.calls)
        return {"decode_tokens_per_s": tokens / elapsed}

    def trace_work(self) -> dict:
        """What the per-layer readers need to count the traced work."""
        return {"kind": "serve", "shapes": self.shapes, "batch": self.batch,
                "prompt_len": self.prompt_len, "new_tokens": self.new}

    def free(self) -> None:
        self.engine.params = None
        del self.engine
        gc.collect()

    # -- correctness: the reference over a sample of served requests -------
    def sample(self):
        """(tokens [k, Tp + new - 1], served [k, new]) of `check_rows`
        requests drawn from the seed among all the window served."""
        k = min(self.traffic["check_rows"], self.attempted())
        pick = rng(self.seed, 2).choice(self.attempted(), k, replace=False)
        toks, served = [], []
        for r in sorted(pick):
            p, out, _, _ = self.calls[r // self.batch]
            i = r % self.batch
            toks.append(np.concatenate([p[i], out[i, :-1]]))
            served.append(out[i])
        return np.stack(toks), np.stack(served)

    def reference_logits(self, tokens, quant: bool = False):
        """f32 reference logits [k * new, V] at the positions that predicted
        each served token, `ref_rows` requests at a time."""
        w = weights.make("dense", self.shapes, self.seed)
        step = self.traffic["ref_rows"]
        cols = np.arange(self.prompt_len - 1, self.prompt_len - 1 + self.new)
        out = []
        for i in range(0, tokens.shape[0], step):
            block = tokens[i:i + step]
            k = block.shape[0]
            out.append(dense_ref.logits_at(
                w, jnp.asarray(block), jnp.asarray(np.repeat(np.arange(k),
                                                             self.new)),
                jnp.asarray(np.tile(cols, k)), eps=self.shapes["norm_eps"],
                theta=self.shapes["rope_theta"], quant=quant))
        return jnp.concatenate(out)

    def check(self, control: str | None = None) -> dict:
        """{"logit_gap": widest gap by which a served token's reference
        logit lies below the reference's best}. With `control`, the tokens
        are those an fp8 reference (`control="fp8"`) puts first at the
        same positions."""
        tokens, served = self.sample()
        ref = self.reference_logits(tokens)
        if control == "fp8":
            lo = self.reference_logits(tokens, quant=True)
            chosen = jnp.argmax(lo, -1)
            del lo
        elif control is None:
            chosen = jnp.asarray(served.reshape(-1))
        else:
            raise ValueError(control)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, chosen[:, None],
                                                     -1)[:, 0]
        return {"logit_gap": float(jnp.max(gap))}
DRIVER = Serve
