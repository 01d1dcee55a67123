"""Production mesh + per-cell logical sharding rules.

Mesh axes:
  * ``pod``   — data parallelism across pods (DCN-friendly: only gradient
    all-reduce crosses pods; FSDP all-gathers stay inside a pod's ICI).
  * ``data``  — in-pod data parallelism / FSDP (parameters' embed dim).
  * ``model`` — tensor parallelism (heads / mlp / experts / vocab).

``rules_for`` maps logical axis names used by the model code to mesh axes
per (arch × shape) cell:

  train/prefill: batch→(pod,data), embed→data (FSDP), heads/mlp/vocab→model
  decode:        batch→(pod,data), kv_seq→model (cache sequence sharding —
                 works for every kv-head count, incl. non-divisible ones)
  long-context:  batch=1 → sequence/state sharding over (data, model)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..configs.base import ArchConfig, ShapeConfig
from ..models.sharding import MeshRules


def _mk_mesh(shape: tuple, axes: tuple):
    """jax.make_mesh with every axis Auto (sharding by annotation)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    return _mk_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def rules_for(cfg: ArchConfig, shape: ShapeConfig, mesh) -> MeshRules:
    """Logical→mesh axis rules for one (arch × shape × mesh) cell."""
    ba = batch_axes(mesh)
    model_size = mesh.shape["model"]

    # Expert parallelism only when experts divide the model axis cleanly;
    # otherwise experts stay replicated and the expert FFN is TP-sharded.
    ep = cfg.n_experts > 0 and cfg.n_experts % model_size == 0

    rules = {
        "batch": ba,
        "seq": None,
        # FSDP: params' d_model dim over data axis (cfg.fsdp=False → pure
        # TP: replicate over data, cutting the weight-grad all-gathers)
        "embed": "data" if cfg.fsdp else None,
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model" if ep else None,
        "moe_mlp": None if ep else "model",
        "capacity": "data",      # MoE dispatch buffer's token-capacity dim
        "d_inner": "model",
        "layers": None,
        "kv_seq": None,
    }

    act_over: dict = {}
    if shape.is_decode:
        # Cache layout: (layers, batch, seq, kv_heads, hd) — shard the
        # sequence axis; uniform across kv-head counts.  The kv_heads
        # rule stays for PARAMS (wk/wv TP) but must not bind cache/attn
        # activations whose kv_seq dim already owns the model axis.
        rules["kv_seq"] = "model"
        act_over["kv_heads"] = None
        if cfg.decode_layout == "replicated" and shape.global_batch > 1:
            # batch-replicated decode: weights stay 2D-sharded (no per-step
            # FSDP gathers); the KV cache spreads over both axes.
            rules["batch"] = None
            rules["kv_seq"] = ("data", "model")
        if shape.global_batch == 1:
            # long_500k: nothing to shard on batch → spread state/sequence
            # over both axes (cache + activations only; params keep the
            # FSDP embed→data rule).
            rules["batch"] = None
            rules["kv_seq"] = ("data", "model")
            act_over["d_inner"] = ("data", "model")
    elif shape.seq_len * shape.global_batch >= 2**20 and shape.kind == "prefill":
        # long prefill: sequence parallelism on activations
        rules["seq"] = None

    return MeshRules(mesh, rules, act_over)


def param_shardings(cfg: ArchConfig, rules: MeshRules):
    """NamedSharding tree for the parameter pytree (shape-aware: mesh
    extents that don't divide a dim are dropped → replicated)."""
    from ..models.model import Spec, schema

    return jax.tree.map(
        lambda s: rules.sharding_for_shape(s.axes, s.shape),
        schema(cfg),
        is_leaf=lambda x: isinstance(x, Spec),
    )


def cache_shardings(cfg: ArchConfig, rules: MeshRules, cache_tree):
    """NamedSharding tree for a decode cache pytree (by array rank/kind).

    Uses the ACTIVATION view of the rules (cache tensors behave like
    activations: kv_seq owns the model axis, kv_heads/d_inner overrides
    apply) with divisibility guards per leaf shape.
    """
    rules = rules.act()

    def spec_for(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = leaf.ndim
        if name in ("k", "v", "c_kv", "k_rope"):
            # (L, B, S, ...) — batch then kv_seq
            axes = ("layers", "batch", "kv_seq") + (None,) * (nd - 3)
        elif name in ("conv", "ssm"):
            # (L, B, ..., d_inner-ish, ...): shard the widest inner dim
            axes = ("layers", "batch") + (None,) * (nd - 3) + ("d_inner",)
            if name == "ssm" and nd == 5:  # (L,B,Hm,P,N) mamba2
                axes = ("layers", "batch", "d_inner", None, None)
            if name == "conv":             # (L,B,K-1,C): channels last
                axes = ("layers", "batch", None, "d_inner")
        elif name in ("attn_k", "attn_v"):
            axes = ("layers", "batch", "kv_seq", None, None)
        elif name == "attn_pos":
            axes = ("layers", "batch", None)
        else:
            axes = (None,) * nd
        return rules.sharding_for_shape(axes, leaf.shape)

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)
