"""Pipeline parallelism (GPipe-style) over a ``stage`` mesh axis.

The model zoo stacks per-layer parameters on a leading ``layers`` axis
(consumed by jax.lax.scan), which makes PP natural in JAX: shard THAT
axis over a ``stage`` mesh axis and run the microbatch rotation inside
shard_map — each device group owns n_layers/n_stages layers and passes
activations to the next stage with ``ppermute``.

Schedule: classic GPipe fill-drain.  T = n_micro + n_stages − 1 ticks;
at tick t, stage s processes microbatch (t − s) when 0 ≤ t−s < n_micro.
Stage 0 injects embeddings; the last stage applies the final norm + LM
head and collects logits.  Bubble fraction = (S−1)/T, amortized by
n_micro — the standard trade recorded in EXPERIMENTS.md §Perf-PP.

Scope: decoder-only dense/GQA families (the PP demo covers stablelm /
qwen / llava / nemotron configs); embedding + head weights are
replicated across stages (their layer placement is an orthogonal
optimization).  Forward-only here — jax.grad differentiates through
shard_map+ppermute, so the same structure trains; the train-step wiring
is left as the documented next step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..compat import shard_map

from ..configs.base import ArchConfig
from ..models import layers as L
from ..models.model import DTYPE, cfg_layers


def _stage_forward(cfg: ArchConfig, p_layers, h, positions):
    """Run this stage's slice of the layer stack (same math as
    model._forward_transformer's scan body, attention cache-less)."""

    def body(carry, p_l):
        x = carry
        a, _ = L.gqa_block(x, p_l["attn"], cfg, positions=positions)
        x = x + a
        y = L.mlp_block(x, p_l["mlp"], cfg)
        return x + y, None

    h, _ = jax.lax.scan(body, h, p_layers)
    return h


def make_pp_prefill_step(cfg: ArchConfig, mesh, n_micro: int = 8):
    """Pipelined prefill: (B, S) tokens → (B, S, vocab) logits.

    Mesh must carry a ``stage`` axis; ``data`` (microbatch rows) and
    ``model`` axes compose as usual inside each stage.
    """
    assert not cfg.mla and not cfg.n_experts and cfg.ssm == "", \
        "PP demo covers the dense/GQA families"
    n_stages = mesh.shape["stage"]
    assert cfg_layers(cfg) % n_stages == 0

    def step(params, batch):
        tokens = batch["tokens"]                   # (B, S) global
        B, S = tokens.shape

        def body(p_layers, embed_w, head_w, fnorm, toks):
            Bl = toks.shape[0]                     # LOCAL batch shard
            assert Bl % n_micro == 0, (Bl, n_micro)
            mb = Bl // n_micro
            stage = jax.lax.axis_index("stage")
            last = n_stages - 1
            positions = jnp.arange(S, dtype=jnp.int32)[None].repeat(mb, 0)
            T = n_micro + n_stages - 1
            d = cfg.d_model

            toks_mb = toks.reshape(n_micro, mb, S)
            out = jnp.zeros((n_micro, mb, S, cfg.vocab), DTYPE)
            cur = jnp.zeros((mb, S, d), DTYPE)     # incoming activation

            def tick(t, carry):
                cur, out = carry
                # stage 0 ingests microbatch t (if still filling)
                m_in = jnp.clip(t, 0, n_micro - 1)
                emb = jnp.take(embed_w, toks_mb[m_in], axis=0).astype(DTYPE)
                h_in = jnp.where(stage == 0, emb, cur)
                active = (t - stage >= 0) & (t - stage < n_micro)
                h = _stage_forward(cfg, p_layers, h_in, positions)
                h = jnp.where(active, h, cur)
                # last stage emits logits for microbatch t - last
                hn = L.norm(h, fnorm, cfg.norm, cfg.norm_eps)
                logits = (hn @ head_w).astype(DTYPE)
                m_out = jnp.clip(t - last, 0, n_micro - 1)
                emit = active & (stage == last)
                out = out.at[m_out].set(
                    jnp.where(emit, logits, out[m_out])
                )
                # rotate activations: stage s → s+1 (ring; wraps ignored)
                nxt = jax.lax.ppermute(
                    h, "stage",
                    [(s, (s + 1) % n_stages) for s in range(n_stages)],
                )
                return nxt, out

            cur, out = jax.lax.fori_loop(0, T, tick, (cur, out))
            # only the last stage holds real logits (zeros elsewhere):
            # reduce over the stage ring so every rank returns the result
            out = jax.lax.psum(out, "stage")
            return out.reshape(Bl, S, cfg.vocab)

        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return shard_map(
            body, mesh=mesh,
            in_specs=(
                P("stage"),                        # layer stack → stages
                P(None, None),                     # embed (replicated)
                P(None, None),                     # head  (replicated)
                P(),                               # final norm
                P("data", None),                   # tokens over data
            ),
            out_specs=P("data", None, None),
            check_vma=False,
        )(params["layers"], params["embed"], head,
          params["final_norm"], tokens)

    return step
