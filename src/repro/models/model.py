"""Model assembly: parameter schema (shapes + logical sharding axes),
init, forward for train/prefill/decode, and KV/SSM cache construction.

One source of truth: ``schema(cfg)`` returns a nested dict of ``Spec``
leaves; ``init_params`` / ``abstract_params`` / ``param_axes`` all traverse
it, so parameter trees and sharding trees can never drift apart.

Layer parameters are stacked with a leading ``layers`` axis and consumed by
``jax.lax.scan`` — essential to keep HLO size O(1) in depth for the 96-layer
/ 340 B dry-run cells.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from . import layers as L
from .sharding import logical


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: tuple
    axes: tuple                 # logical axis names (len == rank)
    init: str = "normal"        # normal | zeros | ones | a_log | a_log2 | dt_bias

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


DTYPE = jnp.bfloat16

# Scan unroll factor for the layer loop.  The dry-run lowers each cell
# twice (unroll=1 and unroll=2): XLA's HloCostAnalysis counts a while-loop
# body ONCE regardless of trip count, so the delta between the two
# lowerings isolates the per-layer body cost for trip-count correction
# (benchmarks/roofline.py).
_SCAN_UNROLL = [1]


@contextlib.contextmanager
def scan_unroll(n: int):
    _SCAN_UNROLL.append(n)
    try:
        yield
    finally:
        _SCAN_UNROLL.pop()


def _unroll() -> int:
    return _SCAN_UNROLL[-1]


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

def _norm_spec(cfg, d, stacked: bool):
    lead = ((cfg_layers(cfg),), ("layers",)) if stacked else ((), ())
    s = {"scale": Spec(lead[0] + (d,), lead[1] + (None,), "zeros")}
    if cfg.norm == "layernorm":
        s["bias"] = Spec(lead[0] + (d,), lead[1] + (None,), "zeros")
    return s


def cfg_layers(cfg):  # stacked-layer count (excludes leading dense layers)
    return cfg.n_layers - cfg.first_dense


def _attn_specs(cfg, n_layers_key="layers"):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    Lk = (cfg_layers(cfg),)
    A = (n_layers_key,)
    s = {
        "norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
        "wq": Spec(Lk + (d, H * hd), A + ("embed", "heads")),
        "wk": Spec(Lk + (d, KV * hd), A + ("embed", "kv_heads")),
        "wv": Spec(Lk + (d, KV * hd), A + ("embed", "kv_heads")),
        "wo": Spec(Lk + (H * hd, d), A + ("heads", "embed")),
    }
    if cfg.norm == "layernorm":
        s["norm"]["bias"] = Spec(Lk + (d,), A + (None,), "zeros")
    if cfg.qkv_bias:
        s["bq"] = Spec(Lk + (H * hd,), A + ("heads",), "zeros")
        s["bk"] = Spec(Lk + (KV * hd,), A + ("kv_heads",), "zeros")
        s["bv"] = Spec(Lk + (KV * hd,), A + ("kv_heads",), "zeros")
    return s


def _mla_specs(cfg):
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    r, rd = cfg.kv_lora_rank, cfg.qk_rope_dim
    vh = cfg.v_head_dim or hd
    Lk = (cfg_layers(cfg),)
    A = ("layers",)
    return {
        "norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
        "wq": Spec(Lk + (d, H * (hd + rd)), A + ("embed", "heads")),
        "w_dkv": Spec(Lk + (d, r + rd), A + ("embed", None)),
        "kv_norm": {"scale": Spec(Lk + (r,), A + (None,), "zeros")},
        "w_uk": Spec(Lk + (r, H * hd), A + (None, "heads")),
        "w_uv": Spec(Lk + (r, H * vh), A + (None, "heads")),
        "wo": Spec(Lk + (H * vh, d), A + ("heads", "embed")),
    }


def _mlp_specs(cfg, f=None, stacked=True):
    d = cfg.d_model
    f = f or cfg.d_ff
    Lk = (cfg_layers(cfg),) if stacked else ()
    A = ("layers",) if stacked else ()
    s = {"norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
         "w1": Spec(Lk + (d, f), A + ("embed", "mlp")),
         "w2": Spec(Lk + (f, d), A + ("mlp", "embed"))}
    if cfg.norm == "layernorm":
        s["norm"]["bias"] = Spec(Lk + (d,), A + (None,), "zeros")
    if cfg.mlp == "swiglu":
        s["w3"] = Spec(Lk + (d, f), A + ("embed", "mlp"))
    return s


def _moe_specs(cfg):
    d, E = cfg.d_model, cfg.n_experts
    fe = cfg.moe_d_ff or cfg.d_ff
    Lk = (cfg_layers(cfg),)
    A = ("layers",)
    s = {
        "norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
        "router": Spec(Lk + (d, E), A + ("embed", None)),
        "w1": Spec(Lk + (E, d, fe), A + ("experts", "embed", "moe_mlp")),
        "w2": Spec(Lk + (E, fe, d), A + ("experts", "moe_mlp", "embed")),
    }
    if cfg.norm == "layernorm":
        s["norm"]["bias"] = Spec(Lk + (d,), A + (None,), "zeros")
    if cfg.mlp == "swiglu":
        s["w3"] = Spec(Lk + (E, d, fe), A + ("experts", "embed", "moe_mlp"))
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        s["shared"] = {
            "w1": Spec(Lk + (d, fs), A + ("embed", "mlp")),
            "w2": Spec(Lk + (fs, d), A + ("mlp", "embed")),
        }
        if cfg.mlp == "swiglu":
            s["shared"]["w3"] = Spec(Lk + (d, fs), A + ("embed", "mlp"))
    return s


def _mamba1_specs(cfg):
    d, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.d_conv
    dt_rank = max(d // 16, 1)
    Lk = (cfg.n_layers,)
    A = ("layers",)
    return {
        "norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
        "in_proj": Spec(Lk + (d, 2 * di), A + ("embed", "d_inner")),
        "conv_w": Spec(Lk + (di, K), A + ("d_inner", None)),
        "conv_b": Spec(Lk + (di,), A + ("d_inner",), "zeros"),
        "x_proj": Spec(Lk + (di, dt_rank + 2 * N), A + ("d_inner", None)),
        "dt_proj": Spec(Lk + (dt_rank, di), A + (None, "d_inner")),
        "dt_bias": Spec(Lk + (di,), A + ("d_inner",), "dt_bias"),
        "A_log": Spec(Lk + (di, N), A + ("d_inner", None), "a_log"),
        "D": Spec(Lk + (di,), A + ("d_inner",), "ones"),
        "out_proj": Spec(Lk + (di, d), A + ("d_inner", "embed")),
    }


def _mamba2_specs(cfg):
    d, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.d_conv
    Hm = cfg.ssm_heads or max(di // 64, 1)
    Lk = (cfg.n_layers,)
    A = ("layers",)
    return {
        "norm": {"scale": Spec(Lk + (d,), A + (None,), "zeros")},
        "in_proj": Spec(Lk + (d, 2 * di + 2 * N + Hm), A + ("embed", "d_inner")),
        "conv_w": Spec(Lk + (di + 2 * N, K), A + ("d_inner", None)),
        "conv_b": Spec(Lk + (di + 2 * N,), A + ("d_inner",), "zeros"),
        "dt_bias": Spec(Lk + (Hm,), A + (None,), "dt_bias"),
        "A_log": Spec(Lk + (Hm,), A + (None,), "a_log2"),
        "D": Spec(Lk + (Hm,), A + (None,), "ones"),
        "norm_gated": {"scale": Spec(Lk + (di,), A + ("d_inner",), "zeros")},
        "out_proj": Spec(Lk + (di, d), A + ("d_inner", "embed")),
    }


def _shared_block_specs(cfg):
    """zamba2's single shared attention+MLP block (unstacked)."""
    d, H, KV, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    s = {
        "attn": {
            "norm": {"scale": Spec((d,), (None,), "zeros")},
            "wq": Spec((d, H * hd), ("embed", "heads")),
            "wk": Spec((d, KV * hd), ("embed", "kv_heads")),
            "wv": Spec((d, KV * hd), ("embed", "kv_heads")),
            "wo": Spec((H * hd, d), ("heads", "embed")),
        },
        "mlp": {
            "norm": {"scale": Spec((d,), (None,), "zeros")},
            "w1": Spec((d, f), ("embed", "mlp")),
            "w2": Spec((f, d), ("mlp", "embed")),
        },
    }
    if cfg.mlp == "swiglu":
        s["mlp"]["w3"] = Spec((d, f), ("embed", "mlp"))
    return s


def _dense0_specs(cfg):
    """Leading dense layers (deepseek ``first_dense``), stacked separately."""
    n = cfg.first_dense
    base_attn = _mla_specs(cfg) if cfg.mla else _attn_specs(cfg)
    base_mlp = _mlp_specs(cfg)

    def restack(tree):
        return jax.tree.map(
            lambda s: Spec((n,) + s.shape[1:], s.axes, s.init), tree,
            is_leaf=lambda x: isinstance(x, Spec),
        )

    return {"attn": restack(base_attn), "mlp": restack(base_mlp)}


def schema(cfg: ArchConfig) -> dict:
    s: dict[str, Any] = {}
    d, V = cfg.d_model, cfg.vocab
    if cfg.uses_tokens:
        v_ax = "vocab" if cfg.embed_vocab_shard else None
        s["embed"] = Spec((V, d), (v_ax, "embed"))
    if cfg.ssm == "mamba1":
        s["layers"] = _mamba1_specs(cfg)
    elif cfg.family == "hybrid":
        s["layers"] = _mamba2_specs(cfg)
        s["shared"] = _shared_block_specs(cfg)
    else:
        block = {"attn": _mla_specs(cfg) if cfg.mla else _attn_specs(cfg)}
        block["moe" if cfg.n_experts else "mlp"] = (
            _moe_specs(cfg) if cfg.n_experts else _mlp_specs(cfg)
        )
        s["layers"] = block
        if cfg.first_dense:
            s["dense0"] = _dense0_specs(cfg)
    s["final_norm"] = {"scale": Spec((d,), (None,), "zeros")}
    if cfg.norm == "layernorm":
        s["final_norm"]["bias"] = Spec((d,), (None,), "zeros")
    if not cfg.tie_embeddings:
        s["lm_head"] = Spec((d, V), ("embed", "vocab"))
    return s


# ---------------------------------------------------------------------------
# init / abstract / axes from schema
# ---------------------------------------------------------------------------

_IS_SPEC = lambda x: isinstance(x, Spec)


def _init_leaf(spec: Spec, key, dtype=DTYPE):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "a_log":           # mamba1: A = -(1..N) per channel
        N = spec.shape[-1]
        a = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), spec.shape[:-1] + (1,))
        return jnp.log(a)
    if spec.init == "a_log2":          # mamba2: A scalar per head in [1, 16]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u)
    if spec.init == "dt_bias":
        dt = jnp.exp(
            jax.random.uniform(key, spec.shape, jnp.float32)
            * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3)
        )
        return jnp.log(jnp.expm1(dt))
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    return (jax.random.normal(key, spec.shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


def init_params(cfg: ArchConfig, key) -> dict:
    leaves, treedef = jax.tree.flatten(schema(cfg), is_leaf=_IS_SPEC)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(s, k) for s, k in zip(leaves, keys)]
    )


def abstract_params(cfg: ArchConfig) -> dict:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape,
            jnp.float32 if s.init in ("a_log", "a_log2", "dt_bias") else DTYPE,
        ),
        schema(cfg), is_leaf=_IS_SPEC,
    )


def param_axes(cfg: ArchConfig) -> dict:
    return jax.tree.map(lambda s: s.axes, schema(cfg), is_leaf=_IS_SPEC)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_in(cfg, params, batch):
    if cfg.uses_tokens:
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(DTYPE)
    else:
        x = batch["embeds"].astype(DTYPE)
    return logical(x, "batch", "seq", "embed")


def _logits_out(cfg, params, x):
    x = L.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head
    return logical(logits, "batch", "seq", "vocab")


def _transformer_block(cfg, p, x, *, positions, cache=None, cache_pos=None,
                       window=0):
    if cfg.mla:
        a, new_c = L.mla_block(x, p["attn"], cfg, positions=positions,
                               cache=cache, cache_pos=cache_pos)
    else:
        a, new_c = L.gqa_block(x, p["attn"], cfg, positions=positions,
                               cache=cache, cache_pos=cache_pos,
                               window=window)
    x = x + a
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        y, aux = L.moe_block(x, p["moe"], cfg)
    else:
        y = L.mlp_block(x, p["mlp"], cfg)
    x = logical(x + y, "batch", "seq", "embed")
    return x, new_c, aux


def forward(cfg: ArchConfig, params, batch, cache=None):
    """Returns (logits, new_cache, aux_loss).

    train/prefill: ``cache is None``; decode: ``cache`` is the stacked
    cache pytree and ``batch['cache_pos']`` the write position.
    """
    x = _embed_in(cfg, params, batch)
    B, S = x.shape[:2]
    decode = cache is not None
    cache_pos = batch.get("cache_pos") if decode else None
    if decode:
        # works for both decode (S=1) and prefill-into-cache (S=prompt)
        positions = jnp.broadcast_to(
            cache_pos + jnp.arange(S, dtype=jnp.int32)[None], (B, S)
        )
    else:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S)
        )

    if cfg.ssm == "mamba1":
        return _forward_mamba(cfg, params, x, cache)
    if cfg.family == "hybrid":
        return _forward_hybrid(cfg, params, x, positions, cache, cache_pos)
    return _forward_transformer(cfg, params, x, positions, cache, cache_pos)


def _forward_transformer(cfg, params, x, positions, cache, cache_pos):
    decode = cache is not None

    if cfg.first_dense:
        for i in range(cfg.first_dense):
            p_i = jax.tree.map(lambda a: a[i], params["dense0"])
            c_i = (jax.tree.map(lambda a: a[i], cache["dense0"])
                   if decode else None)
            x, new_c, _ = _transformer_block(
                cfg, p_i, x, positions=positions, cache=c_i,
                cache_pos=cache_pos,
            )
            if decode:
                cache["dense0"] = jax.tree.map(
                    lambda full, new: full.at[i].set(new),
                    cache["dense0"], new_c,
                )

    def body(carry, xs):
        h, aux_acc = carry
        if decode:
            p_l, c_l = xs
        else:
            p_l, c_l = xs, None
        h, new_c, aux = _transformer_block(
            cfg, p_l, h, positions=positions, cache=c_l, cache_pos=cache_pos,
        )
        return (h, aux_acc + aux), new_c

    body_fn = jax.checkpoint(body) if (cfg.remat and not decode) else body
    xs = (params["layers"], cache["layers"]) if decode else params["layers"]
    (x, aux), new_layer_cache = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), xs, unroll=_unroll()
    )

    logits = _logits_out(cfg, params, x)
    new_cache = None
    if decode:
        new_cache = dict(cache)
        new_cache["layers"] = new_layer_cache
    return logits, new_cache, aux


def _forward_mamba(cfg, params, x, cache):
    decode = cache is not None

    def body(carry, xs):
        h = carry
        if decode:
            p_l, st = xs
        else:
            p_l, st = xs, None
        hin = L.norm(h, p_l["norm"], cfg.norm, cfg.norm_eps)
        y, new_st = L.mamba1_mix(hin, p_l, cfg, state=st)
        return h + y, new_st

    body_fn = jax.checkpoint(body) if (cfg.remat and not decode) else body
    xs = (params["layers"], cache["layers"]) if decode else params["layers"]
    x, new_states = jax.lax.scan(body_fn, x, xs, unroll=_unroll())
    logits = _logits_out(cfg, params, x)
    new_cache = {"layers": new_states} if decode else None
    return logits, new_cache, jnp.zeros((), jnp.float32)


def _forward_hybrid(cfg, params, x, positions, cache, cache_pos):
    """zamba2: scan over mamba2 blocks; one SHARED attention+MLP block is
    applied (with its own per-application KV cache) every ``attn_every``
    blocks.  Sliding-window attention bounds the cache for long contexts."""
    decode = cache is not None
    shared = params["shared"]
    every = cfg.attn_every
    n_apps = cfg.n_layers // every

    def apply_shared(h, app_cache, app_pos):
        a, new_c = L.gqa_block(
            h, shared["attn"], cfg, positions=positions,
            cache=app_cache, cache_pos=app_pos, window=cfg.window,
        )
        h = h + a
        h = h + L.mlp_block(h, shared["mlp"], cfg)
        return h, new_c

    def body(carry, xs):
        if decode:
            (h, i, ak, av, apos) = carry
            p_l, st = xs
        else:
            (h, i) = carry
            p_l, st = xs, None

        hin = L.norm(h, p_l["norm"], cfg.norm, cfg.norm_eps)
        y, new_st = L.mamba2_mix(hin, p_l, cfg, state=st)
        h = h + y

        is_app = ((i % every) == 0) & ((i // every) < n_apps)
        app_idx = jnp.minimum(i // every, n_apps - 1)
        if decode:
            write_pos = cache_pos % cfg.window
            k_cur = jax.lax.dynamic_index_in_dim(ak, app_idx, 0, False)
            v_cur = jax.lax.dynamic_index_in_dim(av, app_idx, 0, False)
            pos_cur = jax.lax.dynamic_index_in_dim(apos, app_idx, 0, False)

            def do_attn(h):
                # attention over this application's rolling-window cache;
                # per-slot absolute positions drive the window mask.
                h2, new_c = L.gqa_block(
                    h, shared["attn"], cfg, positions=positions,
                    cache={"k": k_cur, "v": v_cur, "kpos": pos_cur},
                    cache_pos=write_pos, window=cfg.window,
                )
                pos_new = jax.lax.dynamic_update_slice_in_dim(
                    pos_cur,
                    jnp.broadcast_to(positions[:, :1], pos_cur[:, :1].shape),
                    write_pos, 1,
                )
                h3 = h + h2
                out = h3 + L.mlp_block(h3, shared["mlp"], cfg)
                return out, new_c["k"], new_c["v"], pos_new

            def no_attn(h):
                return h, k_cur, v_cur, pos_cur

            h, k_new, v_new, pos_new = jax.lax.cond(is_app, do_attn, no_attn, h)
            ak = jax.lax.dynamic_update_index_in_dim(ak, k_new, app_idx, 0)
            av = jax.lax.dynamic_update_index_in_dim(av, v_new, app_idx, 0)
            apos = jax.lax.dynamic_update_index_in_dim(apos, pos_new, app_idx, 0)
            return (h, i + 1, ak, av, apos), new_st

        def do_attn_t(h):
            a, _ = L.gqa_block(h, shared["attn"], cfg, positions=positions,
                               window=cfg.window)
            h = h + a
            return h + L.mlp_block(h, shared["mlp"], cfg)

        h = jax.lax.cond(is_app & (app_idx < n_apps), do_attn_t, lambda h: h, h)
        return (h, i + 1), new_st

    body_fn = jax.checkpoint(body) if (cfg.remat and not decode) else body
    if decode:
        carry0 = (x, jnp.int32(0), cache["attn_k"], cache["attn_v"],
                  cache["attn_pos"])
        (x, _, ak, av, apos), new_states = jax.lax.scan(
            body_fn, carry0, (params["layers"], cache["layers"]),
            unroll=_unroll(),
        )
        new_cache = {"layers": new_states, "attn_k": ak, "attn_v": av,
                     "attn_pos": apos}
    else:
        (x, _), _ = jax.lax.scan(
            body_fn, (x, jnp.int32(0)), params["layers"], unroll=_unroll()
        )
        new_cache = None
    logits = _logits_out(cfg, params, x)
    return logits, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=None) -> dict:
    """Decode-time state for one request batch.

    KV leaves use ``cfg.kv_dtype`` (fp8 halves decode HBM traffic — the
    on-chip analogue of plane-proportional fetch); SSM/conv recurrent
    state stays at full precision (it is rewritten, not appended).
    """
    dtype = dtype or jnp.dtype(cfg.kv_dtype)
    n = cfg_layers(cfg)
    if cfg.ssm == "mamba1":
        di, N, K = cfg.d_inner, cfg.ssm_state, cfg.d_conv
        return {"layers": {
            "conv": jnp.zeros((cfg.n_layers, batch, K - 1, di), dtype),
            "ssm": jnp.zeros((cfg.n_layers, batch, di, N), jnp.float32),
        }}
    if cfg.family == "hybrid":
        di, N, K = cfg.d_inner, cfg.ssm_state, cfg.d_conv
        Hm = cfg.ssm_heads or max(di // 64, 1)
        P_ = di // Hm
        W = min(cfg.window or max_seq, max_seq)
        n_apps = cfg.n_layers // cfg.attn_every
        KV, hd = cfg.n_kv_heads, cfg.hd
        return {
            "layers": {
                "conv": jnp.zeros((cfg.n_layers, batch, K - 1, di + 2 * N), dtype),
                "ssm": jnp.zeros((cfg.n_layers, batch, Hm, P_, N), jnp.float32),
            },
            "attn_k": jnp.zeros((n_apps, batch, W, KV, hd), dtype),
            "attn_v": jnp.zeros((n_apps, batch, W, KV, hd), dtype),
            "attn_pos": jnp.full((n_apps, batch, W), -2 * (cfg.window or 1),
                                 jnp.int32),
        }
    if cfg.mla:
        r, rd = cfg.kv_lora_rank, cfg.qk_rope_dim
        out = {"layers": {
            "c_kv": jnp.zeros((n, batch, max_seq, r), dtype),
            "k_rope": jnp.zeros((n, batch, max_seq, 1, rd), dtype),
        }}
    else:
        KV, hd = cfg.n_kv_heads, cfg.hd
        out = {"layers": {
            "k": jnp.zeros((n, batch, max_seq, KV, hd), dtype),
            "v": jnp.zeros((n, batch, max_seq, KV, hd), dtype),
        }}
    if cfg.first_dense:
        out["dense0"] = jax.tree.map(
            lambda a: jnp.zeros((cfg.first_dense,) + a.shape[1:], a.dtype),
            out["layers"],
        )
    return out


# ---------------------------------------------------------------------------
# losses / step functions
# ---------------------------------------------------------------------------

def lm_loss(cfg: ArchConfig, params, batch):
    """Next-token CE for decoders; masked-frame CE for encoder-only."""
    logits, _, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    if cfg.causal:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    loss = -jnp.mean(ll)
    return loss + 0.01 * aux


def prefill(cfg: ArchConfig, params, batch, max_seq: int):
    """Run the full prompt, returning (logits, populated cache).

    Attention caches are filled by recomputing K/V into the cache buffer;
    for SSM/hybrid the final state is produced by the scan itself.  For
    dry-run purposes prefill = forward (cache population is fused)."""
    logits, _, aux = forward(cfg, params, batch)
    return logits


def decode_step(cfg: ArchConfig, params, batch, cache):
    """One token across the batch with a populated cache."""
    logits, new_cache, _ = forward(cfg, params, batch, cache=cache)
    return logits, new_cache
