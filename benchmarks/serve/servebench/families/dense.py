"""Dense decoder family: GQA attention with RoPE, SwiGLU MLP, RMSNorm.

Covers Qwen2 (QKV bias, tied embeddings) and Mistral (no bias, untied
head).  Everything the benchmark needs of one family lives here:

* :func:`arch_config` maps the published ``config.json`` keys of a
  configuration file onto the served program's ``ArchConfig``;
* :func:`make_params` draws the served weights from ``--seed`` on the
  device, in bf16, in one jitted call, in the program's parameter layout;
* :func:`reference_logits` is the plain float32 forward pass of the
  published architecture, written from its description with nothing
  imported from the program.  It reads the benchmark-made weights by the
  layout's key names only;
* the operation and byte counts that rooflines and utilisation use.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# -- the published configuration --------------------------------------------


def dims(conf: dict) -> dict:
    """Sizes of the published configuration under short names."""
    c = conf["config"]
    d, H = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"], "d": d, "H": H,
        "KV": c["num_key_value_heads"], "hd": c.get("head_dim") or d // H,
        "f": c["intermediate_size"], "V": c["vocab_size"],
        "tied": bool(c.get("tie_word_embeddings", False)),
        "bias": bool(c.get("attention_bias", c.get("model_type") == "qwen2")),
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
    }


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.configs.base import ArchConfig

    m = dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["KV"], d_ff=m["f"], vocab=m["V"],
        head_dim=m["hd"], qkv_bias=m["bias"], tie_embeddings=m["tied"],
        rope_theta=m["theta"], mlp="swiglu", norm="rmsnorm", remat=False,
    )


# -- operation and byte counts ----------------------------------------------


def layer_params(conf: dict) -> int:
    m = dims(conf)
    d, hd = m["d"], m["hd"]
    attn = d * m["H"] * hd + 2 * d * m["KV"] * hd + m["H"] * hd * d
    bias = (m["H"] + 2 * m["KV"]) * hd if m["bias"] else 0
    return attn + bias + 3 * d * m["f"] + 2 * d


def param_count(conf: dict) -> int:
    """Every parameter: embedding, layers, final norm, untied head."""
    m = dims(conf)
    emb = m["V"] * m["d"] * (1 if m["tied"] else 2)
    return emb + m["L"] * layer_params(conf) + m["d"]


def matmul_params(conf: dict) -> int:
    """Parameters that take part in a matrix product for every token:
    the layers' projections and the output head (not the lookup)."""
    m = dims(conf)
    d, hd = m["d"], m["hd"]
    per = d * m["H"] * hd + 2 * d * m["KV"] * hd + m["H"] * hd * d \
        + 3 * d * m["f"]
    return m["L"] * per + m["V"] * d


def kv_bytes_per_token(conf: dict, elem_bytes: int = 2) -> int:
    m = dims(conf)
    return m["L"] * 2 * m["KV"] * m["hd"] * elem_bytes


def token_flops(conf: dict, ctx: int) -> int:
    """Model FLOPs of one token that attends to ``ctx`` positions
    (itself included): two per matmul parameter, plus QK^T and PV."""
    m = dims(conf)
    return 2 * matmul_params(conf) + 4 * m["L"] * m["H"] * m["hd"] * ctx


def span_flops(conf: dict, start: int, n: int) -> int:
    """FLOPs of ``n`` tokens at positions ``start .. start+n-1``."""
    m = dims(conf)
    ctx_sum = n * (start + 1) + n * (n - 1) // 2
    return 2 * matmul_params(conf) * n + 4 * m["L"] * m["H"] * m["hd"] * ctx_sum


def decode_bytes(conf: dict, ctx: int, elem_bytes: int = 2) -> int:
    """Bytes one decode step needs: every weight it reads once, plus the
    bf16 KV of the ``ctx`` positions it attends to.  An untied embedding
    table gives one row; a tied one is read whole as the output head."""
    m = dims(conf)
    unread = 0 if m["tied"] else (m["V"] - 1) * m["d"]
    return ((param_count(conf) - unread) * elem_bytes
            + kv_bytes_per_token(conf, elem_bytes) * ctx)


# -- weights from the seed ---------------------------------------------------


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number, 64 bits and more."""
    k = jax.random.key(0)
    s = int(seed) % (1 << 64)
    for part in (s >> 48, (s >> 32) & 0xFFFF, (s >> 16) & 0xFFFF, s & 0xFFFF):
        k = jax.random.fold_in(k, part)
    return k


def _leaf_std(path: str, shape: tuple) -> float:
    """Scale of a leaf by its role: unit-variance products for weights,
    ``1 + N(0, 0.1)`` norm weights, visible QKV biases."""
    if path.endswith("scale"):
        return 0.1
    if path.split("/")[-1] in ("bq", "bk", "bv"):
        return 0.5
    if path.endswith("embed"):
        return 1.0 / math.sqrt(shape[-1])
    return 1.0 / math.sqrt(shape[-2])


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def make_params(acfg, seed: int):
    """The served weights, drawn from ``seed`` on the device in bf16, in
    the program's parameter layout, in one jitted call."""
    from repro.models import abstract_params

    names, leaves, treedef = _paths(abstract_params(acfg))
    specs = tuple((n, tuple(a.shape)) for n, a in zip(names, leaves))
    return jax.tree_util.tree_unflatten(
        treedef, list(_draw(specs, seed_key(seed))))


@functools.partial(jax.jit, static_argnums=0)
def _draw(specs, key):
    out = []
    for i, (name, shape) in enumerate(specs):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((z * _leaf_std(name, shape)).astype(jnp.bfloat16))
    return out


# -- the plain reference -----------------------------------------------------

_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _q(x, low: bool):
    """``x`` as the control's precision would hold it: float8 e4m3 with
    one scale per tensor; unchanged for the float32 reference."""
    if not low:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / s).astype(_FP8).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    return jnp.matmul(_q(a, low), _q(w.astype(jnp.float32), low))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotary embedding, rotate-half form (HF ``apply_rotary_pos_emb``).
    x: (T, heads, hd); pos: (T,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(m, x, layers, i, low):
    """One decoder layer over the whole sequence x: (T, d) float32."""
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
    T = x.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]
    pos = jnp.arange(T)
    h = _rms(x, 1.0 + p["attn"]["norm"]["scale"], m["eps"])
    q, k, v = (_mm(h, p["attn"][w], low) for w in ("wq", "wk", "wv"))
    if m["bias"]:
        q, k, v = q + p["attn"]["bq"], k + p["attn"]["bk"], v + p["attn"]["bv"]
    q = _rope(q.reshape(T, H, hd), pos, m["theta"])
    k = _rope(k.reshape(T, KV, hd), pos, m["theta"])
    v = v.reshape(T, KV, hd)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)            # head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _q(q, low), _q(k, low)) / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _q(a, low), _q(v, low)).reshape(T, H * hd)
    x = x + _mm(o, p["attn"]["wo"], low)
    h = _rms(x, 1.0 + p["mlp"]["norm"]["scale"], m["eps"])
    g = jax.nn.silu(_mm(h, p["mlp"]["w1"], low)) * _mm(h, p["mlp"]["w3"], low)
    return x + _mm(g, p["mlp"]["w2"], low)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(m, x, params, rows, low):
    h = _rms(x[rows], 1.0 + params["final_norm"]["scale"].astype(jnp.float32),
             m["eps"])
    w = params["embed"].T if m["tied"] else params["lm_head"]
    return _mm(h, w, low)


@functools.partial(jax.jit, static_argnums=0)
def _embed(m, params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def _hashable(m: dict) -> tuple:
    return tuple(sorted(m.items()))


class _Dims(dict):
    """A dict of sizes that jit can take as a static argument."""

    def __hash__(self):
        return hash(_hashable(self))


def reference_logits(conf: dict, params, tokens: np.ndarray,
                     rows: np.ndarray, low: bool = False) -> np.ndarray:
    """Float32 logits of the published architecture at sequence
    positions ``rows`` of ``tokens``, under ``highest`` matmul precision.

    ``tokens`` is padded by the caller to a fixed length (positions
    after the real sequence never reach earlier ones, by causality), and
    ``rows`` to a fixed count, so that a cell compiles a few shapes.
    ``low=True`` computes the control instead: the same pass with every
    matrix product's operands held in float8 e4m3.
    """
    m = _Dims(dims(conf))
    with jax.default_matmul_precision("highest"):
        x = _embed(m, params, jnp.asarray(tokens, jnp.int32))
        for i in range(m["L"]):
            x = _layer(m, x, params["layers"], i, low)
        out = _head(m, x, params, jnp.asarray(rows, jnp.int32), low)
    return np.asarray(out)
