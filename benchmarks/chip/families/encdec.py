"""Whisper-style encoder-decoder: parameter tree, batches, FLOPs, reference.

Everything here is the benchmark's own.  The parameter tree has the keys
and stacked-layer layout the program's ``repro.models.encdec`` reads, so
the same arrays feed the program and the reference; the reference loss
below is written from the architecture (arXiv:2212.04356) with the
program's documented departures (stub frontend, sinusoidal decoder
positions, tanh GELU), in float32, and imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: configuration key -> the program's ``ArchConfig`` field
PROGRAM_KEYS = {
    "d_model": "d_model",
    "decoder_layers": "n_layers",
    "encoder_layers": "n_encoder_layers",
    "decoder_attention_heads": "n_heads",
    "decoder_ffn_dim": "d_ff",
    "vocab_size": "vocab_size",
    "max_source_positions": "n_frames",
}

#: sizes of the CPU rehearsal (control flow only, never a measurement)
REHEARSE = {
    "d_model": 32, "encoder_layers": 1, "decoder_layers": 1,
    "encoder_attention_heads": 2, "decoder_attention_heads": 2,
    "encoder_ffn_dim": 64, "decoder_ffn_dim": 64, "vocab_size": 128,
    "max_source_positions": 16, "max_target_positions": 8,
}


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for ``cfg``; refuses a mismatch."""
    import dataclasses
    from repro.configs import get_config
    base = get_config(cfg["program"])
    arch = dataclasses.replace(
        base, n_kv_heads=cfg["decoder_attention_heads"], head_dim=0,
        **{field: cfg[key] for key, field in PROGRAM_KEYS.items()})
    if cfg["encoder_attention_heads"] != cfg["decoder_attention_heads"] \
            or cfg["encoder_ffn_dim"] != cfg["decoder_ffn_dim"]:
        raise ValueError("the program shares heads and ffn width between "
                         "encoder and decoder")
    if arch.activation != "gelu" or arch.norm != "layernorm" \
            or not arch.qkv_bias or arch.rope != "none":
        raise ValueError(f"program config {arch.name} is not whisper-like")
    return arch


# ------------------------------------------------------------------ shapes
def _attn(d: int, bias: bool = True) -> dict:
    out = {k: {"w": (d, d)} for k in ("q", "k", "v", "o")}
    if bias:
        for k in ("q", "k", "v"):
            out[k]["b"] = (d,)
    return out


def _norm(d: int) -> dict:
    return {"scale": (d,), "bias": (d,)}


def param_shapes(cfg: dict) -> dict:
    d, ff, vocab = cfg["d_model"], cfg["decoder_ffn_dim"], cfg["vocab_size"]
    mlp = {"in": {"w": (d, ff)}, "out": {"w": (ff, d)}}
    enc = {"norm1": _norm(d), "attn": _attn(d), "norm2": _norm(d),
           "mlp": mlp}
    dec = {"norm1": _norm(d), "self": _attn(d), "norm_x": _norm(d),
           "cross": _attn(d), "norm2": _norm(d), "mlp": mlp}

    def stack(tree, n):
        return jax.tree.map(lambda s: (n,) + s, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": {"table": (vocab, d)},
        "enc_layers": stack(enc, cfg["encoder_layers"]),
        "dec_layers": stack(dec, cfg["decoder_layers"]),
        "enc_norm": _norm(d),
        "final_norm": _norm(d),
        "lm_head": {"w": (d, vocab)},
    }


def init_params(key, cfg: dict) -> dict:
    """float32 weights from ``key``: matrices N(0, 1/fan_in), the
    embedding N(0, 1/d), norms at scale 1 and bias 0, biases 0."""
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if name.endswith("['scale']"):
            out.append(jnp.ones(shape, jnp.float32))
        elif name.endswith("['b']") or name.endswith("['bias']"):
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            fan_in = shape[-1] if "embed" in name else shape[-2]
            out.append(jax.random.normal(k, shape, jnp.float32)
                       / math.sqrt(fan_in))
    return jax.tree.unflatten(treedef, out)


def make_batch(key, cfg: dict, n: int, b: int, s: int) -> dict:
    """One step's batch, split over ``n`` workers of ``b`` examples:
    bfloat16 frames and a uniform token stream, labels the next tokens."""
    kf, kt = jax.random.split(key)
    frames = jax.random.normal(
        kf, (n, b, cfg["max_source_positions"], cfg["d_model"]),
        jnp.bfloat16)
    stream = jax.random.randint(kt, (n, b, s + 1), 0, cfg["vocab_size"],
                                jnp.int32)
    return {"frames": frames, "tokens": stream[..., :-1],
            "labels": stream[..., 1:]}


def half_batch(batch: dict) -> dict:
    """Each worker's first half of its examples, twice: the same shapes,
    with the mean over the batch taken over that half alone."""
    def half(x):
        h = x.shape[1] // 2
        return jnp.concatenate([x[:, :h], x[:, :h]], axis=1)
    return jax.tree.map(half, batch)


# ------------------------------------------------------------------- FLOPs
def step_flops(cfg: dict, n: int, b: int, s: int) -> float:
    """FLOPs that ``n`` workers' forward and backward passes require per
    step: matmuls and attention products, causal attention counted half,
    backward twice the forward, no recomputation, the stub frontend not
    counted."""
    d, ff, vocab = cfg["d_model"], cfg["decoder_ffn_dim"], cfg["vocab_size"]
    frames = cfg["max_source_positions"]
    enc_tok = (2 * 4 * d * d + 2 * 2 * d * ff     # q, k, v, o; mlp
               + 2 * 2 * frames * d)               # scores and values
    dec_tok = (2 * 4 * d * d + 2 * 2 * s * d / 2   # self attention, causal
               + 2 * 2 * d * d                     # cross q, o
               + 2 * 2 * frames * d                # cross scores, values
               + 2 * 2 * d * ff)                   # mlp
    cross_kv = 2 * 2 * d * d * frames              # per sequence and layer
    per_seq = (cfg["encoder_layers"] * frames * enc_tok
               + cfg["decoder_layers"] * (s * dec_tok + cross_kv)
               + 2 * s * d * vocab)                # lm head
    return 3.0 * n * b * per_seq


# --------------------------------------------------------------- reference
def _sinusoids(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-math.log(10000.0) * 2.0
                  * jnp.arange(d // 2, dtype=jnp.float32)[None, :] / d)
    return jnp.concatenate([jnp.sin(pos * inv), jnp.cos(pos * inv)], -1)


def _layernorm(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _linear(dot, p, x):
    y = dot("bsi,io->bso", x, p["w"])
    return y + p["b"] if "b" in p else y


def _attention(dot, p, x, mem, heads: int, causal: bool):
    b, s, d = x.shape
    hd = d // heads

    def split(y):
        return y.reshape(y.shape[0], y.shape[1], heads, hd)
    q = split(_linear(dot, p["q"], x))
    k = split(_linear(dot, p["k"], mem))
    v = split(_linear(dot, p["v"], mem))
    logits = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        keep = jnp.tril(jnp.ones((s, mem.shape[1]), bool))
        logits = jnp.where(keep, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = dot("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    return _linear(dot, p["o"], out)


def _mlp(dot, p, x):
    return _linear(dot, p["out"], jax.nn.gelu(_linear(dot, p["in"], x)))


def reference_loss(params: dict, cfg: dict, batch: dict, dot) -> jax.Array:
    """Mean next-token cross entropy of one worker's batch, float32.
    ``dot(subscripts, a, b)`` is every matrix product of the pass."""
    heads = cfg["decoder_attention_heads"]
    x = batch["frames"].astype(jnp.float32)
    x = x + _sinusoids(x.shape[1], x.shape[2])[None]
    for i in range(cfg["encoder_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["enc_layers"])
        h = _layernorm(lp["norm1"], x)
        x = x + _attention(dot, lp["attn"], h, h, heads, causal=False)
        x = x + _mlp(dot, lp["mlp"], _layernorm(lp["norm2"], x))
    mem = _layernorm(params["enc_norm"], x)

    tokens = batch["tokens"]
    y = jnp.take(params["embed"]["table"], tokens, axis=0)
    y = y + _sinusoids(tokens.shape[1], y.shape[2])[None]
    for i in range(cfg["decoder_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["dec_layers"])
        h = _layernorm(lp["norm1"], y)
        y = y + _attention(dot, lp["self"], h, h, heads, causal=True)
        y = y + _attention(dot, lp["cross"], _layernorm(lp["norm_x"], y),
                           mem, heads, causal=False)
        y = y + _mlp(dot, lp["mlp"], _layernorm(lp["norm2"], y))
    y = _layernorm(params["final_norm"], y)
    logits = dot("bsi,io->bso", y, params["lm_head"]["w"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
