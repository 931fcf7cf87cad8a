"""Training driver: `Trainer.run`, one step a call, over seeded token
batches (every row of every step differs), until the window closes.

Traffic parameters: batch, seq, policy, control_path ("in-graph": the
policy is compiled into the step; "host": it runs between steps through
`HostRailController`), lr (constant AdamW rate), optimizer (AdamW's
settings), trace_seconds, check_steps (the first steps the reference
follows).

Set-up builds one trainer, drives it from the seed through its first
`check_steps` steps through the same `run` call and feed as the window,
and reads on the way what the check compares: each step's loss, each
leaf's first gradient as the optimizer got it (from AdamW's first moment
after one step), and each leaf's change over those steps (before the next
step takes the parameters).
"""

from __future__ import annotations

import gc
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.drivers.common import program_config, rng, span
from bench.reference import hybrid as hybrid_ref

# bench leaf name -> the program's leaf path
PROGRAM_LEAF = {"embed": "embed", "lm_head": "lm_head",
                "final_norm": "final_norm_w", "layers/ln": "blocks/ln1_w",
                "shared/ln1": "shared/ln1_w", "shared/ln2": "shared/ln2_w",
                "shared/w_gate": "shared/mlp/w_gate",
                "shared/w_up": "shared/mlp/w_in",
                "shared/w_down": "shared/mlp/w_out"}
for _n in ("wq", "wk", "wv", "wo"):
    PROGRAM_LEAF[f"shared/{_n}"] = f"shared/attn/{_n}"
for _n in ("w_z", "w_x", "w_B", "w_C", "w_dt", "conv_x_w", "conv_x_b",
           "conv_B_w", "conv_B_b", "conv_C_w", "conv_C_b", "A_log", "D",
           "dt_bias", "norm_w", "w_out"):
    PROGRAM_LEAF[f"layers/{_n}"] = f"blocks/mamba/{_n}"


def program_tree(w: dict, Vp: int) -> dict:
    """Bench hybrid weights in the program's layout (vocabulary rows padded
    with zeros)."""
    out: dict = {}
    for name, path in PROGRAM_LEAF.items():
        a = hybrid_ref.leaf(w, name)
        if name == "embed":
            a = jnp.pad(a, ((0, Vp - a.shape[0]), (0, 0)))
        elif name == "lm_head":
            a = jnp.pad(a, ((0, 0), (0, Vp - a.shape[1])))
        node = out
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = a
    return out


def _path_sq(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p, simple=True, separator="/"): a
            for p, a in flat}


class Feed:
    """The trainer's data: step s is a [batch, seq] block of tokens drawn
    from the seed, uniform over the vocabulary, and its next tokens."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.batch, self.seq, self.vocab = seed, batch, seq, vocab

    def host_batch(self, step: int):
        t = rng(self.seed, 3, step).integers(
            0, self.vocab, (self.batch, self.seq + 1), dtype=np.int32)
        return t[:, :-1], t[:, 1:]

    def jax_batch(self, step: int):
        tokens, labels = self.host_batch(step)
        return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}


class Train:
    def __init__(self, cell: dict, seed: int):
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed = seed
        self.shapes = self.config["shapes"]
        self.cfg = program_config(self.config)
        t = self.traffic
        self.batch, self.seq = t["batch"], t["seq"]
        self.steps = []          # (start, end) of each window step
        self.readings: dict = {}

    def build(self):
        from repro.core.control_plane import HostRailController
        from repro.core.policy import POLICIES
        from repro.core.power_plane import PowerPlaneState, StepProfile
        from repro.models import registry
        from repro.optim import adamw
        from repro.train.step import (StepConfig, jit_train_step,
                                      make_train_step)
        from repro.train.trainer import Trainer, TrainerConfig

        t, cfg, seed, Vp = self.traffic, self.cfg, self.seed, \
            self.cfg.vocab_padded
        api = registry.build(cfg, remat="full")
        shapes = self.shapes
        params = program_params(shapes, seed, Vp)
        want = jax.eval_shape(api.init, jax.random.key(0))
        got = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(got) or want != got:
            raise ValueError("the program's parameter layout is not the one "
                             "bench/drivers/train.py lays its weights out in")
        self.n_params = sum(int(a.size) for a in
                            jax.tree_util.tree_leaves(params))
        o = t["optimizer"]
        opt_cfg = adamw.AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                    weight_decay=o["weight_decay"],
                                    grad_clip_norm=o["grad_clip_norm"])
        self.opt_cfg = opt_cfg
        tokens = self.batch * self.seq
        n = self.n_params
        profile = StepProfile(6.0 * n * tokens, 14.0 * n, 4.0 * n, 4.0 * n)
        policy = POLICIES[t["policy"]]
        in_graph = t["control_path"] == "in-graph"
        lr = t["lr"]
        step = jit_train_step(make_train_step(
            lambda p, b: api.loss_fn(p, b), opt_cfg,
            lambda s: jnp.float32(lr) + 0.0 * s, profile,
            StepConfig(policy=policy if in_graph else None)))
        self.feed = Feed(seed, self.batch, self.seq, shapes["vocab"])
        controller = None if in_graph else HostRailController(policy)
        self.trainer = Trainer(step, self.feed, TrainerConfig(
            total_steps=0, ckpt_every=0,
            ckpt_dir=str(Path(__file__).resolve().parents[1] / ".ckpt"),
            controller=controller),
            {"params": params, "opt": adamw.init_state(params, opt_cfg),
             "plane": PowerPlaneState.nominal(), "ef": None})

    def run_steps(self, upto: int) -> None:
        """`Trainer.run` from its next step through step `upto` - 1."""
        tr = self.trainer
        tr.start_step = len(tr.log.records)
        tr.cfg.total_steps = upto
        tr.run()

    # -- set-up: the first steps, read for the check -----------------------
    def setup(self) -> None:
        self.build()
        tr, n_check = self.trainer, self.traffic["check_steps"]
        # the starting weights wait on the host: a second copy on the
        # device would leave its freed space among the window's buffers
        p0 = jax.device_get(_path_sq(tr.state["params"]))
        self.run_steps(1)
        rec = tr.log.records[0]
        gnorm = float(rec.extras["grad_norm"])
        clip = min(1.0, self.opt_cfg.grad_clip_norm / (gnorm + 1e-9))
        m = _path_sq(tr.state["opt"]["m"])
        sq = {k: float(v) for k, v in
              zip(m, _sum_sq(list(m.values())))}
        b1 = self.opt_cfg.b1
        self.readings["grad"] = {
            name: math.sqrt(sq[path]) / (1 - b1) / clip
            for name, path in PROGRAM_LEAF.items()}
        self.run_steps(n_check)
        p = _path_sq(tr.state["params"])
        self.readings["change"] = {
            name: math.sqrt(float(_diff_sq(p[path], p0[path])))
            for name, path in PROGRAM_LEAF.items()}
        self.readings["loss"] = [float(r.loss) for r in
                                 list(tr.log.records)[:n_check]]

    # -- the measured window ----------------------------------------------
    def window(self, seconds: float, clock) -> float:
        t0 = clock.now()
        while True:
            s = clock.now()
            with span("bench.step"):
                self.run_steps(len(self.trainer.log.records) + 1)
            e = clock.now()
            self.steps.append((s, e))
            if e - t0 >= seconds:
                return e - t0

    def durations(self) -> list:
        """Host seconds of each step of the window."""
        return [x[-1] - x[-2] for x in self.steps]

    def attempted(self) -> int:
        return len(self.steps)

    def failed(self) -> int:
        recs = list(self.trainer.log.records)[-len(self.steps):]
        return sum(1 for r in recs if not math.isfinite(float(r.loss)))

    def end_to_end(self, elapsed: float) -> dict:
        return {"train_tokens_per_s":
                len(self.steps) * self.batch * self.seq / elapsed}

    def trace_work(self) -> dict:
        return {"kind": "train", "shapes": self.shapes, "batch": self.batch,
                "seq": self.seq}

    def free(self) -> None:
        self.trainer.state.clear()
        del self.trainer
        gc.collect()

    # -- correctness --------------------------------------------------------
    def reference(self, quant: bool = False, rows=None) -> dict:
        """The f32 reference's readings over the same first steps: losses,
        first gradient norm of each leaf, each leaf's change."""
        n_check = self.traffic["check_steps"]
        batches = [tuple(jnp.asarray(a) for a in self.feed.host_batch(s))
                   for s in range(n_check)]
        w = weights.make("hybrid", self.shapes, self.seed)
        losses, grads = hybrid_ref.train(
            w, batches, [self.traffic["lr"]] * n_check, self.shapes,
            self.traffic["optimizer"], quant=quant, rows=rows)
        w0 = weights.make("hybrid", self.shapes, self.seed)
        change = {n: float(jnp.sqrt(jnp.sum(jnp.square(
            hybrid_ref.leaf(w, n).astype(jnp.float32)
            - hybrid_ref.leaf(w0, n).astype(jnp.float32)))))
            for n in PROGRAM_LEAF}
        return {"loss": losses, "grad": grads, "change": change}

    def check(self, control: str | None = None) -> dict:
        """The three numbers compared, for the program's readings or, with
        `control` ("fp8", "half_batch"), for a reference variant put in the
        program's place."""
        if "ref" not in self.readings:
            self.readings["ref"] = self.reference()
        ref = self.readings["ref"]
        if control is None:
            got = self.readings
        elif control == "fp8":
            got = self.readings[control] = self.reference(quant=True)
        elif control == "half_batch":
            got = self.readings[control] = self.reference(
                rows=np.arange(self.batch // 2))
        else:
            raise ValueError(control)
        return compare(got, ref)


def compare(got: dict, ref: dict) -> dict:
    """loss_gap: the widest relative gap of a step's loss. grad_gap and
    change_gap: over leaves, the gap between the program's norm and the
    reference's, over the larger of the reference's norm of that leaf and
    of the median leaf, at the worst leaf; grad_gap_median: that gap of
    the first gradient at the median leaf, which one small leaf's noise
    does not move. Leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of
    change_gap."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"],
                                                       ref["loss"]))

    def gaps(key, names):
        med = float(np.median([ref[key][n] for n in names]))
        return med, {n: abs(got[key][n] - ref[key][n]) / max(ref[key][n], med)
                     for n in names}

    def worst(key, names):
        med, g = gaps(key, names)
        n = max(g, key=g.get)
        return g[n], [n, got[key][n], ref[key][n], med]

    names = sorted(ref["grad"])
    gmed = float(np.median([ref["grad"][n] for n in names]))
    moving = [n for n in names if ref["grad"][n] >= 1e-3 * gmed]
    grad_gap, grad_leaf = worst("grad", names)
    change_gap, change_leaf = worst("change", moving)
    # the worst leaves, [name, program, reference, median], for the record
    grad_gap_median = float(np.median(list(gaps("grad", names)[1].values())))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_gap_median": grad_gap_median, "change_gap": change_gap,
            "worst": {"grad": grad_leaf, "change": change_leaf}}


@jax.jit
def _sum_sq(leaves):
    return [jnp.sum(jnp.square(a.astype(jnp.float32))) for a in leaves]


@jax.jit
def _diff_sq(leaf, start):
    """Squared norm of one leaf's change; `start` comes from the host."""
    return jnp.sum(jnp.square(leaf.astype(jnp.float32)
                              - start.astype(jnp.float32)))


def program_params(shapes: dict, seed: int, Vp: int):
    """The bench's hybrid weights in the program's layout, made by the same
    compiled program as the reference's (so both start from the same
    bits), then laid out in a second."""
    return _layout(weights.make("hybrid", shapes, seed), Vp)


_layout = jax.jit(program_tree, static_argnums=1, donate_argnums=0)

DRIVER = Train
