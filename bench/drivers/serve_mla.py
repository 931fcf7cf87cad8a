"""Serving driver for a DeepSeek-V3-block model (latent attention, held
routed experts, shared experts): `bench.drivers.serve`'s closed loop of
back-to-back `ServeEngine.generate` calls over `bench.weights_mla`'s
weights, checked against `bench.reference.mla_moe`.

Traffic parameters as in `bench.drivers.serve`. The traced work adds the
window's mean, per decode step, of the engine's held-expert counters
(`moe_experts_hit`, `moe_pairs_held`, each summed over the MoE layers).

The check reads, over the sampled served tokens, the gap by which each
one's reference logit lies below the reference's best: the widest
(`logit_gap`) and the mean (`logit_gap_mean`). In bfloat16 a near-tie
between the sixth and seventh expert's scores now and then routes a token
otherwise than the f32 reference does, which can open one position's gap
as wide as a lower precision opens it; a lower precision opens many.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_mla
from bench.drivers.common import rng
from bench.drivers.serve import Serve, build_engine
from bench.reference import mla_moe as mla_ref

COUNTERS = ("moe_experts_hit", "moe_pairs_held")


def program_params(cfg, shapes: dict, seed: int):
    """The bench's weights laid out as the program stores them: W_kv_b split
    into its K and V halves, the vocabulary padded with zero rows."""
    N, Vp = shapes["qk_nope_head_dim"], cfg.vocab_padded

    def attention(lay):
        return {"wq": lay["wq"], "wkv_a": lay["wkv_a"],
                "kv_norm_w": lay["kv_norm"],
                "wk_b": lay["wkv_b"][..., :N], "wv_b": lay["wkv_b"][..., N:],
                "wo": lay["wo"]}

    def swiglu(m):
        return {"w_gate": m["w_gate"], "w_in": m["w_up"],
                "w_out": m["w_down"]}

    @functools.partial(jax.jit, donate_argnums=0)
    def layout(w):
        d, m, V = w["dense_layers"], w["moe_layers"], w["embed"].shape[0]
        return {
            "embed": jnp.pad(w["embed"], ((0, Vp - V), (0, 0))),
            "final_norm_w": w["final_norm"],
            "lm_head": jnp.pad(w["lm_head"], ((0, 0), (0, Vp - V))),
            "dense_blocks": {"ln1_w": d["ln1"], "attn": attention(d),
                             "ln2_w": d["ln2"], "mlp": swiglu(d)},
            "blocks": {"ln1_w": m["ln1"], "attn": attention(m),
                       "ln2_w": m["ln2"],
                       "moe": {"router": m["router"], "e_bias": m["e_bias"],
                               **swiglu(m["experts"]),
                               "shared": swiglu(m["shared"])}},
        }

    return layout(weights_mla.make(shapes, seed))


class ServeMla(Serve):
    def setup(self) -> None:
        params = program_params(self.cfg, self.shapes, self.seed)
        self.engine = build_engine(self.cfg, self.traffic, params)
        out = self.engine.generate(self.prompts(0), min(2, self.new))
        jnp.concatenate([jnp.asarray(out[:, :1])] * self.new, axis=1)
        self._counted = self._counters()

    def _counters(self) -> dict:
        return {k: getattr(self.engine.stats, k) for k in COUNTERS}

    def trace_work(self) -> dict:
        steps = len(self.calls) * (self.new - 1)
        now = self._counters()
        return {**super().trace_work(),
                **{k: (now[k] - self._counted[k]) / steps if steps else None
                   for k in COUNTERS}}

    def reference_logits(self, tokens, quant: bool = False):
        """f32 reference logits [k * new, V] at the positions that predicted
        each served token, `ref_rows` requests at a time."""
        w = weights_mla.make(self.shapes, self.seed)
        shapes = tuple(sorted(self.shapes.items()))
        step = self.traffic["ref_rows"]
        cols = np.arange(self.prompt_len - 1, self.prompt_len - 1 + self.new)
        out = []
        for i in range(0, tokens.shape[0], step):
            block = tokens[i:i + step]
            k = block.shape[0]
            out.append(mla_ref.logits_at(
                w, jnp.asarray(block),
                jnp.asarray(np.repeat(np.arange(k), self.new)),
                jnp.asarray(np.tile(cols, k)), shapes=shapes, quant=quant))
        return jnp.concatenate(out)

    def check(self, control: str | None = None) -> dict:
        """{"logit_gap", "logit_gap_mean"} of the served tokens. With
        `control`, the tokens are those an fp8 reference puts first at the
        same positions (`"fp8"`), or the served ones with one position of
        each row, drawn from the seed, given a token drawn uniformly from
        the vocabulary (`"one_token"`)."""
        tokens, served = self.sample()
        ref = self.reference_logits(tokens)
        chosen = served.reshape(-1)
        if control == "fp8":
            chosen = jnp.argmax(self.reference_logits(tokens, quant=True), -1)
        elif control == "one_token":
            g = rng(self.seed, 3)
            chosen = served.copy()
            rows = np.arange(served.shape[0])
            chosen[rows, g.integers(0, self.new, len(rows))] = g.integers(
                0, self.shapes["vocab"], len(rows))
            chosen = chosen.reshape(-1)
        elif control is not None:
            raise ValueError(control)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(
            ref, jnp.asarray(chosen)[:, None], -1)[:, 0]
        return {"logit_gap": float(jnp.max(gap)),
                "logit_gap_mean": float(jnp.mean(gap))}


DRIVER = ServeMla
