"""Cells at a size a CPU test can hold, built like the real ones."""

from __future__ import annotations

import copy

from bench import spec

DENSE = {"vocab": 500, "d_model": 64, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 4, "head_dim": 16, "d_ff": 96, "norm_eps": 1e-05,
         "rope_theta": 10000.0}
# the serving control test's size: a widest gap swings with the sample, so
# it scores as many served tokens as a full-size run (8 rows x 64)
SMALL_DENSE = dict(DENSE, n_layers=4, d_model=128, head_dim=32, d_ff=256,
                   vocab=4000)
HYBRID = {"vocab": 300, "d_model": 64, "n_layers": 4, "n_heads": 4,
          "head_dim": 16, "d_ff": 96, "d_state": 16, "ssm_head_dim": 64,
          "expand": 2, "n_groups": 1, "conv_width": 4, "attn_every": 2,
          "norm_eps": 1e-05, "rope_theta": 10000.0}


def dense_program(s: dict) -> dict:
    # tp 16 keeps the program's head padding (4 heads -> 16 slots)
    return {"arch": "minicpm_2b", "overrides": {
        "n_layers": s["n_layers"], "d_model": s["d_model"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_kv_heads"],
        "d_ff": s["d_ff"], "vocab_size": s["vocab"], "tp": 16}}


def hybrid_program(s: dict) -> dict:
    return {"arch": "zamba2_1p2b", "overrides": {
        "n_layers": s["n_layers"], "d_model": s["d_model"],
        "n_heads": s["n_heads"], "n_kv_heads": s["n_heads"],
        "d_ff": s["d_ff"], "vocab_size": s["vocab"],
        "ssm_state": s["d_state"], "attn_every": s["attn_every"],
        "head_dim": s["head_dim"], "tp": 1, "remat_group": 2}}


def cell(name: str, shapes: dict | None = None, **traffic) -> dict:
    """The real cell `name`, its configuration and traffic cut to a tiny
    size (shapes and traffic keys overridable)."""
    c = copy.deepcopy(spec.cell(spec.benchmark(), name))
    cfg = c["config"]
    if cfg["weights"] == "dense":
        cfg["shapes"] = dict(shapes or DENSE)
        cfg["program"] = dense_program(cfg["shapes"])
        c["traffic"].update(batch=2, prompt_len=8, new_tokens=4,
                            check_rows=2)
    else:
        cfg["shapes"] = dict(shapes or HYBRID)
        cfg["program"] = hybrid_program(cfg["shapes"])
        c["traffic"].update(batch=4, seq=64)
    c["traffic"].update(traffic)
    return c
