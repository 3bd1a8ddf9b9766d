"""Mamba-1 (falcon-mamba): the gradient leaves of a chip's share.

Only the leaf shapes are needed: the configuration is run by the
aggregation round, which sees the float32 gradient of each parameter.
The tree has the program's mamba1 layout (``repro.models.ssm``), layers
stacked on a leading axis.
"""
from __future__ import annotations

#: sizes of the CPU rehearsal (control flow only, never a measurement)
REHEARSE = {"hidden_size": 32, "intermediate_size": 64, "state_size": 4,
            "time_step_rank": 4, "vocab_size": 96, "num_hidden_layers": 1}


def param_shapes(cfg: dict) -> dict:
    d, di = cfg["hidden_size"], cfg["intermediate_size"]
    if di != cfg["expand"] * d:
        raise ValueError("intermediate_size must be expand * hidden_size")
    st, k, r = cfg["state_size"], cfg["conv_kernel"], cfg["time_step_rank"]
    vocab, n = cfg["vocab_size"], cfg["num_hidden_layers"]
    layer = {
        "mamba": {
            "in_proj": {"w": (d, 2 * di)},
            "conv_w": (k, di),
            "conv_b": (di,),
            "x_proj": {"w": (di, r + 2 * st)},
            "dt_proj": {"w": (r, di), "b": (di,)},
            "A_log": (di, st),
            "D": (di,),
            "out_proj": {"w": (di, d)},
        },
        "norm1": {"scale": (d,)},
    }
    stacked = _stack(layer, n)
    return {"embed": {"table": (vocab, d)}, "layers": stacked,
            "final_norm": {"scale": (d,)}, "lm_head": {"w": (d, vocab)}}


def _stack(tree, n: int):
    if isinstance(tree, tuple):
        return (n,) + tree
    return {key: _stack(v, n) for key, v in tree.items()}
