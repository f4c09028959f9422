"""Mixture-of-Experts transformer (mixtral-8x7b, phi3.5-moe).

Token-choice top-k routing with *sort-based* dispatch: assignments are sorted
by expert id, positioned with a cumsum-of-counts, capacity-dropped, and
scattered into an (E, C, D) buffer -- no (N, E, C) one-hot tensor is ever
materialized, so dispatch is O(N k D) memory and the expert matmuls dominate
FLOPs (this is what keeps MODEL_FLOPS/HLO_FLOPS honest in the roofline).

Two execution paths:
  * local (single device / GSPMD-friendly fallback used in smoke tests);
  * shard_map tensor-parallel: batch sharded over the data axes, expert d_ff
    sharded over the model axis, partial down-projections psum-reduced --
    used whenever ``rt.mesh`` is set (the production path).

Expert FFNs are the paper's "many MCA tiles" picture 1:1; their kernels are
named "w" so :func:`repro.models.rram.program_rram` can put them on the analog
backend, and the EC path is honored inside the expert einsums.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.crossbar import matmul
from .common import (
    Runtime, attention, attention_specs, cross_entropy_loss,
    embed_spec, rmsnorm, rmsnorm_spec, unembed_spec, _k_stencil,
)
from .params import spec, stack_specs
from . import transformer as base

__all__ = ["init_specs", "loss", "prefill", "decode_step", "moe_apply"]


def moe_specs(cfg: ModelConfig) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": {"w": spec((d, e), ("embed", None), scale=0.02)},
        "wg": {"w": spec((e, d, f), ("expert", "embed", "mlp"))},
        "wu": {"w": spec((e, d, f), ("expert", "embed", "mlp"))},
        "wd": {"w": spec((e, f, d), ("expert", "mlp", "embed"))},
    }


def layer_specs(cfg: ModelConfig) -> Dict:
    return {
        "ln_attn": rmsnorm_spec(cfg.d_model),
        "attn": attention_specs(cfg),
        "ln_mlp": rmsnorm_spec(cfg.d_model),
        "moe": moe_specs(cfg),
    }


def init_specs(cfg: ModelConfig) -> Dict:
    s = {
        "embed": embed_spec(cfg.vocab_pad, cfg.d_model),
        "layers": stack_specs(cfg.n_layers, layer_specs(cfg)),
        "ln_f": rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = unembed_spec(cfg.d_model, cfg.vocab_pad)
    return s


# --------------------------------------------------------------------------- #
# Dispatch / combine
# --------------------------------------------------------------------------- #

def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(cfg.experts_per_token * n_tokens
                  * cfg.expert_capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _expert_mm(pd: Dict, x: jnp.ndarray, rt: Optional[Runtime]) -> jnp.ndarray:
    """x (E, C, D) @ w (E, D, F), honoring the RRAM EC backend."""
    w = pd["w"]
    if rt is None or rt.rram is None or not rt.rram.enabled or "w_tilde" not in pd:
        return matmul(x, w.astype(x.dtype))
    cfg = rt.rram
    from .common import _encode_act
    xt = _encode_act(x, rt.next_key(), cfg) if cfg.encode_inputs else x
    if cfg.ec:
        out = (matmul(x, pd["w_tilde"].astype(x.dtype))
               + matmul(xt, pd["dw"].astype(x.dtype)))
        o32 = out.astype(jnp.float32)
        return (o32 - cfg.lam * _k_stencil(o32, -1.0)).astype(x.dtype)
    return matmul(xt, pd["w_tilde"].astype(x.dtype))


MOE_TOKEN_CHUNK = 8192


def _moe_ffn_local(p: Dict, x2: jnp.ndarray, cfg: ModelConfig,
                   rt: Optional[Runtime]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x2 (N, D) -> (out (N, D), aux).  Long token streams (32k prefill) run
    through lax.map over fixed-size chunks so the (E, C, D) dispatch buffers
    stay bounded regardless of sequence length."""
    n, d = x2.shape
    ch = MOE_TOKEN_CHUNK
    if n > ch and n % ch == 0:
        xs = x2.reshape(n // ch, ch, d)
        outs, auxs = jax.lax.map(
            lambda xc: _moe_ffn_chunk(p, xc, cfg, rt), xs)
        return outs.reshape(n, d), jnp.mean(auxs)
    return _moe_ffn_chunk(p, x2, cfg, rt)


def _moe_ffn_chunk(p: Dict, x2: jnp.ndarray, cfg: ModelConfig,
                   rt: Optional[Runtime]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n, d = x2.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(n, cfg)

    gates = jax.nn.softmax(
        (x2 @ p["router"]["w"].astype(x2.dtype)).astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                       # (N, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    ef = topi.reshape(-1)                                      # (N*k,)
    order = jnp.argsort(ef, stable=True)
    es = ef[order]
    counts = jnp.bincount(ef, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(es.shape[0], dtype=jnp.int32) - starts[es].astype(jnp.int32)
    keep = pos < cap
    slot = jnp.where(keep, es * cap + pos, e * cap)

    xs = x2[(order // k)]
    buf = jnp.zeros((e * cap + 1, d), x2.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xs, 0))
    xin = buf[:-1].reshape(e, cap, d)

    h = jax.nn.silu(_expert_mm(p["wg"], xin, rt)) * _expert_mm(p["wu"], xin, rt)
    yout = _expert_mm(p["wd"], h, rt)                          # (E, C, D)

    ys = yout.reshape(e * cap, d)
    got = jnp.where(keep[:, None], ys[jnp.minimum(slot, e * cap - 1)], 0)
    inv = jnp.argsort(order, stable=True)
    out_assign = got[inv].reshape(n, k, d)
    out = jnp.sum(out_assign * topv[..., None].astype(x2.dtype), axis=1)

    # Switch-style load-balance aux: E * sum_e f_e * P_e.
    f_e = jnp.bincount(ef, length=e).astype(jnp.float32) / (n * k)
    p_e = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return out, aux


def moe_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
              rt: Optional[Runtime]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, T, D) -> (out, aux).  shard_map TP path when rt.mesh is set."""
    b, t, d = x.shape

    if rt is None or rt.mesh is None:
        out, aux = _moe_ffn_local(p, x.reshape(b * t, d), cfg, rt)
        return out.reshape(b, t, d), aux

    mesh = rt.mesh
    mp = rt.model_axis
    # Batch must divide the data axes to shard it; tiny batches (long-context
    # decode with B=1) run replicated across the data axes instead.
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dsz = 1
    for ax in rt.batch_axes:
        dsz *= sizes.get(ax, 1)
    batch_spec = rt.batch_axes if b % dsz == 0 else None

    def local(x_l, router, wg, wu, wd):
        pl = {"router": router, "wg": wg, "wu": wu, "wd": wd}
        bl, tl, _ = x_l.shape
        out_l, aux_l = _moe_ffn_local(pl, x_l.reshape(bl * tl, d), cfg, rt)
        # wg/wu/wd are sharded on d_ff over the model axis: the down-proj
        # partials must be summed across it (tensor parallelism).
        out_l = jax.lax.psum(out_l, axis_name=mp)
        aux_l = jax.lax.pmean(aux_l, axis_name=mp)
        if batch_spec is not None:
            for ax in rt.batch_axes:
                aux_l = jax.lax.pmean(aux_l, axis_name=ax)
        return out_l.reshape(bl, tl, d), aux_l

    in_specs = (
        P(batch_spec, None, None),
        jax.tree.map(lambda _: P(None, None), p["router"]),
        jax.tree.map(lambda _: P(None, None, mp), p["wg"]),
        jax.tree.map(lambda _: P(None, None, mp), p["wu"]),
        jax.tree.map(lambda _: P(None, mp, None), p["wd"]),
    )
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(batch_spec, None, None), P()),
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return out, aux


# --------------------------------------------------------------------------- #
# Model interface
# --------------------------------------------------------------------------- #

init_caches = base.init_caches


def layer_apply(lp, x, cfg, rt, positions, cache):
    from .common import constrain_batch
    x = constrain_batch(x, rt)
    a, cache = attention(lp["attn"], rmsnorm(lp["ln_attn"], x, cfg.norm_eps),
                         cfg, rt, positions=positions, cache=cache)
    x = x + a
    m, aux = moe_apply(lp["moe"], rmsnorm(lp["ln_mlp"], x, cfg.norm_eps), cfg, rt)
    return x + m, cache, aux


def forward(params, tokens, cfg, rt, positions=None, caches=None):
    from .common import constrain_batch
    cd = jnp.dtype(cfg.compute_dtype)
    x = constrain_batch(params["embed"].astype(cd)[tokens], rt)
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]

    if caches is None:
        def body(carry, lp):
            h, aux_acc = carry
            h, _, aux = layer_apply(lp, h, cfg, rt, positions, None)
            return (h, aux_acc + aux), None
        fn = body
        if getattr(rt, "remat", "none") in ("block", "full"):
            fn = jax.checkpoint(body, prevent_cse=False)
        (x, aux_sum), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                                       params["layers"])
        new_caches = None
    else:
        def body(carry, xs):
            h, aux_acc = carry
            lp, cache = xs
            h, cache, aux = layer_apply(lp, h, cfg, rt, positions, cache)
            return (h, aux_acc + aux), cache
        (x, aux_sum), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["layers"], caches))
    return rmsnorm(params["ln_f"], x, cfg.norm_eps), new_caches, aux_sum


def loss(params, batch, cfg, rt, aux_weight: float = 0.01):
    hidden, _, aux = forward(params, batch["tokens"], cfg, rt)
    logits = base.logits_fn(params, hidden, cfg, rt)
    return cross_entropy_loss(logits, batch["labels"]) + aux_weight * aux / max(cfg.n_layers, 1)


def prefill(params, batch, cfg, rt, max_len):
    tokens = batch["tokens"]
    b, t = tokens.shape
    caches = base.init_caches(b, max_len, cfg)
    hidden, caches, _ = forward(params, tokens, cfg, rt, caches=caches)
    return base.logits_fn(params, hidden[:, -1:], cfg, rt), caches


def decode_step(params, tokens, caches, cfg, rt):
    cur = caches["len"][0]
    positions = jnp.broadcast_to(cur[None, None], tokens.shape).astype(jnp.int32)
    hidden, caches, _ = forward(params, tokens, cfg, rt,
                                positions=positions, caches=caches)
    return base.logits_fn(params, hidden, cfg, rt), caches
