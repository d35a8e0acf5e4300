"""Plain reference for the Qwen3 dense decoder, and the weights it is served with.

The reference follows the published ``Qwen3ForCausalLM`` (transformers'
``modeling_qwen3``): RMSNorm before attention and MLP, q/k RMSNorm over each
head, rotary embedding in the rotate-half layout (dimension ``i`` pairs with
``i + head_dim/2``), grouped-query causal attention, SwiGLU MLP, final RMSNorm,
LM head (the embedding when ``tie_word_embeddings``).  It is written in
``jax.numpy`` from the published configuration alone, computes in float32 at
``Precision.HIGHEST`` and imports nothing of the program under test.

Weights are random, made from the seed leaf by leaf and layer by layer
(``hf_leaf``), so the reference regenerates each layer's weights when it needs
them and never reads an array that was handed to the program.  ``program_params``
lays the same values out the way the program stores them, as a checkpoint
loader would: one stacked leaf per layer kind, and the rotary pairs of ``q``,
``k`` and their norms interleaved (the program rotates dimension ``2i`` with
``2i + 1``); ``program_config`` maps the published configuration onto the
fields of the program's model config.  Neither imports the program.

``precision="int8"`` is the lower-precision control: every linear layer runs
int8 x int8 -> int32 with per-token activation and per-output-channel weight
scales (W8A8), attention in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM_SPREAD = 0.1        # norm weights are 1 + NORM_SPREAD * N(0, 1)

# leaf name -> (index folded into the key, shape from the dims)
_LAYER_LEAVES = {
    "q_proj": (0, lambda d: (d["D"], d["H"] * d["hd"])),
    "k_proj": (1, lambda d: (d["D"], d["KV"] * d["hd"])),
    "v_proj": (2, lambda d: (d["D"], d["KV"] * d["hd"])),
    "o_proj": (3, lambda d: (d["H"] * d["hd"], d["D"])),
    "gate_proj": (4, lambda d: (d["D"], d["F"])),
    "up_proj": (5, lambda d: (d["D"], d["F"])),
    "down_proj": (6, lambda d: (d["F"], d["D"])),
    "input_layernorm": (7, lambda d: (d["D"],)),
    "post_attention_layernorm": (8, lambda d: (d["D"],)),
    "q_norm": (9, lambda d: (d["hd"],)),
    "k_norm": (10, lambda d: (d["hd"],)),
}
_TOP_LEAVES = {
    "embed_tokens": (100, lambda d: (d["V"], d["D"])),
    "lm_head": (101, lambda d: (d["D"], d["V"])),
    "norm": (102, lambda d: (d["D"],)),
}


def dims(cfg: dict) -> dict:
    """Sizes from a published ``config.json``."""
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
            "tied": bool(cfg["tie_word_embeddings"]),
            "dtype": jnp.dtype(cfg["torch_dtype"])}


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also past 32 bits."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def hf_leaf(d: dict, key: jax.Array, name: str, layer=0) -> jax.Array:
    """One weight in the published layout (``x @ W``), in the served dtype.
    Matrices: truncated normal / sqrt(fan_in); norms: around one."""
    idx, shape_of = (_LAYER_LEAVES | _TOP_LEAVES)[name]
    shape = shape_of(d)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer)
    if len(shape) == 1:
        w = 1.0 + NORM_SPREAD * jax.random.normal(k, shape, jnp.float32)
    else:
        fan_in = d["D"] if name == "embed_tokens" else shape[0]
        w = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) \
            * fan_in ** -0.5
    return w.astype(d["dtype"])


def _interleave_rope(w: jax.Array, hd: int) -> jax.Array:
    """Last axis (head_dim, rotate-half order) -> interleaved pairs:
    position 2i takes dimension i, 2i+1 takes i + hd/2."""
    perm = np.stack([np.arange(hd // 2), np.arange(hd // 2) + hd // 2],
                    axis=1).reshape(-1)
    return jnp.take(w, jnp.asarray(perm), axis=-1)


def program_config(cfg: dict) -> dict:
    """The fields of the program's model config that the published
    ``config.json`` sets, under the program's names; the rest (the layer
    pattern) comes from its registered entry ``cfg["program_config"]``."""
    return {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
            "head_dim": cfg["head_dim"], "qk_norm": True,
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "rope_theta": float(cfg["rope_theta"]),
            "dtype": cfg["torch_dtype"]}


def program_params(cfg: dict, key: jax.Array) -> dict:
    """The served weights (``key`` from ``base_key(seed)``) in the program's
    parameter tree.  Call under ``jax.jit`` (with ``out_shardings`` to make
    them sharded); layers are made one at a time, so no more than one
    layer's float32 values exist at once."""
    d = dims(cfg)
    layers = jnp.arange(d["L"])

    def stacked(name):
        return jax.lax.map(lambda i: hf_leaf(d, key, name, i), layers)

    L, D, H, KV, hd = d["L"], d["D"], d["H"], d["KV"], d["hd"]
    mixer = {
        "wq": _interleave_rope(stacked("q_proj").reshape(L, D, H, hd), hd),
        "wk": _interleave_rope(stacked("k_proj").reshape(L, D, KV, hd), hd),
        "wv": stacked("v_proj").reshape(L, D, KV, hd),
        "wo": stacked("o_proj").reshape(L, H, hd, D),
        "q_norm": _interleave_rope(stacked("q_norm"), hd),
        "k_norm": _interleave_rope(stacked("k_norm"), hd),
    }
    ffn = {"w_gate": stacked("gate_proj"), "w_up": stacked("up_proj"),
           "w_down": stacked("down_proj")}
    out = {"embed": hf_leaf(d, key, "embed_tokens"),
           "final_norm": hf_leaf(d, key, "norm"),
           "group0": {"sub0": {"norm1": stacked("input_layernorm"),
                               "norm2": stacked("post_attention_layernorm"),
                               "mixer": mixer, "ffn": ffn}}}
    if not d["tied"]:
        out["head"] = hf_leaf(d, key, "lm_head")
    return out


# ---------------------------------------------------------------------------
# the reference forward
# ---------------------------------------------------------------------------
def _quant(x: jax.Array, axis: int):
    """Symmetric int8 along ``axis``: (values, f32 scale)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) \
        / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _linear(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (..., K) f32 @ w (K, N) -> f32."""
    if precision == "int8":
        xq, xs = _quant(x, -1)
        wq, ws = _quant(w.astype(jnp.float32), 0)
        y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * xs * ws
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding; x (B, S, heads, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(x, mask, key, layer, *, cfg_items, precision):
    """One decoder layer over a batch of sequences, x (B, S, D) f32."""
    d = dims(dict(cfg_items))
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]

    def w(name):
        return hf_leaf(d, key, name, layer)

    h = _rmsnorm(x, w("input_layernorm"), d["eps"])
    q = _linear(h, w("q_proj"), precision).reshape(B, S, H, hd)
    k = _linear(h, w("k_proj"), precision).reshape(B, S, KV, hd)
    v = _linear(h, w("v_proj"), precision).reshape(B, S, KV, hd)
    q = _rmsnorm(q, w("q_norm"), d["eps"])
    k = _rmsnorm(k, w("k_norm"), d["eps"])
    pos = jnp.arange(S)
    q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
    att_dt = jnp.bfloat16 if precision == "int8" else jnp.float32
    causal = pos[:, None] >= pos[None, :]

    def attend(args):
        # one sequence at a time: the (H, S, S) scores of a whole batch
        # would not fit next to the weights
        q1, k1, v1, m1 = args
        k1 = jnp.repeat(k1, H // KV, axis=1).astype(att_dt)
        v1 = jnp.repeat(v1, H // KV, axis=1).astype(att_dt)
        s = jnp.einsum("qhd,khd->hqk", q1.astype(att_dt), k1,
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        s = jnp.where(causal[None] & m1[None, None, :], s * hd ** -0.5,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(att_dt), v1,
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    o = jax.lax.map(attend, (q, k, v, mask))
    x = x + _linear(o.reshape(B, S, H * hd), w("o_proj"), precision)
    h = _rmsnorm(x, w("post_attention_layernorm"), d["eps"])
    g = _linear(h, w("gate_proj"), precision)
    u = _linear(h, w("up_proj"), precision)
    return x + _linear(jax.nn.silu(g) * u, w("down_proj"), precision)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key, *, cfg_items):
    d = dims(dict(cfg_items))
    return jnp.take(hf_leaf(d, key, "embed_tokens"), tokens, axis=0) \
        .astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(x, key, *, cfg_items, precision):
    """Final norm and LM head on the gathered rows x (N, D) -> (N, V)."""
    d = dims(dict(cfg_items))
    x = _rmsnorm(x, hf_leaf(d, key, "norm"), d["eps"])
    head = hf_leaf(d, key, "embed_tokens").T if d["tied"] \
        else hf_leaf(d, key, "lm_head")
    return _linear(x, head, precision)


def forward_logits(cfg: dict, seed: int, seqs: list, rows: list,
                   precision: str = "f32", batch: int = 0,
                   length: int = 0) -> list:
    """Teacher-forced logits.  ``seqs``: token lists; ``rows[i]``: the
    positions of ``seqs[i]`` whose next-token logits are wanted.  The
    sequences run together as one zero-padded (``batch``, ``length``) batch
    (fixed sizes, so that every run reuses one compiled program), layer by
    layer, with each layer's weights made afresh from the seed.  Returns one
    f32 (len(rows[i]), V) numpy array per sequence."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    d = dims(cfg)
    key = base_key(seed)
    B = max(batch, len(seqs))
    S = -(-max([length] + [len(s) for s in seqs]) // 128) * 128
    tok = np.zeros((B, S), np.int32)
    mask = np.zeros((B, S), bool)
    mask[:, 0] = True             # padding rows attend one token, not none
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
        mask[i, :len(s)] = True
    x = _embed(jnp.asarray(tok), key, cfg_items=items)
    mask = jnp.asarray(mask)
    for layer in range(d["L"]):
        x = _layer(x, mask, key, jnp.int32(layer), cfg_items=items,
                   precision=precision)
    # the head's row count is rounded up too, for the same reason
    flat = np.zeros(-(-sum(len(r) for r in rows) // 512) * 512, np.int64)
    at = 0
    for i, r in enumerate(rows):
        flat[at:at + len(r)] = i * S + np.asarray(r, np.int64)
        at += len(r)
    logits = np.asarray(_head(x.reshape(-1, d["D"])[jnp.asarray(flat)], key,
                              cfg_items=items, precision=precision))
    out, at = [], 0
    for r in rows:
        out.append(logits[at:at + len(r)])
        at += len(r)
    return out
