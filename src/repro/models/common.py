"""Shared model components: linear ops (digital + RRAM analog backend), norms,
RoPE, GQA attention (qk-norm / sliding-window / cross-attn / KV cache), MLPs,
embeddings, and the cross-entropy loss.

All linear kernels are 2-D ``(d_in, d_out)`` and named ``"w"`` -- that is the
contract that lets :func:`repro.models.rram.program_rram` swap any layer onto
the analog backend (the paper's technique) without model-specific code.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RRAMBackendConfig
from repro.core.crossbar import matmul
from .params import ParamSpec, spec

__all__ = [
    "Runtime", "dense", "dense_spec", "rmsnorm", "rmsnorm_spec", "layernorm",
    "layernorm_spec", "rope", "attention_specs", "attention", "init_kv_cache",
    "mlp_specs", "mlp", "embed_spec", "unembed_spec", "cross_entropy_loss",
    "sinusoidal_positions",
]


# --------------------------------------------------------------------------- #
# Runtime context (threads the RRAM backend + rng through apply functions)
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class Runtime:
    """Per-call context. ``key`` may be a tracer; ``_salt`` is a trace-time
    counter giving each dense call site its own fold_in salt."""

    rram: Optional[RRAMBackendConfig] = None
    key: Optional[jax.Array] = None
    mesh: Any = None                    # for shard_map layers (MoE)
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    flash_threshold: int = 512 * 512    # t*s above which attention chunks
    q_chunk: int = 1024
    kv_chunk: int = 1024
    causal_skip: bool = False           # static skip of masked KV chunks
    remat: str = "none"                 # none | block | full
    attn_in_dtype: str = "native"       # "native": bf16 operands + fp32 MXU
    #   accumulation (preferred_element_type); "f32": cast K/V to fp32 before
    #   the einsum (costs a full-cache fp32 round-trip -- kept for the perf
    #   ablation in EXPERIMENTS.md section Perf).
    _salt: int = 0

    def next_key(self) -> jax.Array:
        self._salt += 1
        base = self.key if self.key is not None else jax.random.PRNGKey(0)
        return jax.random.fold_in(base, self._salt)


def constrain_batch(x: jnp.ndarray, rt: Optional["Runtime"]) -> jnp.ndarray:
    """Pin activations to batch-over-data sharding (GSPMD left alone will
    sometimes replicate the microbatch; MaxText-style boundary constraints
    keep every layer's working set 1/dp-sized)."""
    if rt is None or rt.mesh is None:
        return x
    sizes = dict(zip(rt.mesh.axis_names, rt.mesh.devices.shape))
    dsz = 1
    for a in rt.batch_axes:
        dsz *= sizes.get(a, 1)
    if x.shape[0] % dsz != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(rt.batch_axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(rt.mesh, spec))


def _k_stencil(p: jnp.ndarray, h: float) -> jnp.ndarray:
    """(L^T L) p along the last axis (row-0 diagonal is 1, see core.ec)."""
    up = jnp.concatenate([p[..., 1:], jnp.zeros_like(p[..., :1])], axis=-1)
    dn = jnp.concatenate([jnp.zeros_like(p[..., :1]), p[..., :-1]], axis=-1)
    kp = (1.0 + h * h) * p + h * (up + dn)
    first = kp[..., :1] - (h * h) * p[..., :1]
    return jnp.concatenate([first, kp[..., 1:]], axis=-1)


def _encode_act(x: jnp.ndarray, key: jax.Array, cfg: RRAMBackendConfig) -> jnp.ndarray:
    """DAC-side encoding noise on activations (x -> x_tilde)."""
    from repro.core.devices import effective_sigma_py, get_device
    sigma = effective_sigma_py(get_device(cfg.device), cfg.k_iters)
    eta = jax.random.normal(key, x.shape, dtype=x.dtype)
    return x * (1.0 + jnp.asarray(sigma, x.dtype) * eta)


def dense_spec(d_in: int, d_out: int, axes=("embed", "mlp"), scale=None) -> Dict:
    return {"w": spec((d_in, d_out), axes, scale=scale)}


def dense(p: Dict, x: jnp.ndarray, rt: Optional[Runtime] = None) -> jnp.ndarray:
    """y = x @ w.  If the layer has been programmed onto the RRAM backend
    (``w_tilde``/``dw`` present), runs the two-tier error-corrected analog path:

        tier-1 (fused):  p = x @ W_tilde + x_tilde @ (W - W_tilde)
        tier-2:          y = p - lam * (L^T L) p        (truncated Neumann)
    """
    w = p["w"]
    if rt is None or rt.rram is None or not rt.rram.enabled or "w_tilde" not in p:
        return matmul(x, w)
    cfg = rt.rram
    cd = x.dtype
    xt = _encode_act(x, rt.next_key(), cfg) if cfg.encode_inputs else x
    if cfg.ec:
        out = (matmul(x, p["w_tilde"].astype(cd))
               + matmul(xt, p["dw"].astype(cd)))
        out32 = out.astype(jnp.float32)
        out = (out32 - cfg.lam * _k_stencil(out32, -1.0)).astype(cd)
    else:
        out = matmul(xt, p["w_tilde"].astype(cd))
    return out


# --------------------------------------------------------------------------- #
# Norms, RoPE, positions
# --------------------------------------------------------------------------- #

def rmsnorm_spec(d: int) -> Dict:
    return {"scale": spec((d,), ("embed",), init="ones")}


def rmsnorm(p: Dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_spec(d: int) -> Dict:
    return {"scale": spec((d,), ("embed",), init="ones"),
            "bias": spec((d,), ("embed",), init="zeros")}


def layernorm(p: Dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _rope_trig(positions: jnp.ndarray, theta: float, dh: int):
    """Full-width (Dh) cos / signed-sin tables, built from iota -- never by
    concatenating computed half-width arrays (see :func:`rope`)."""
    half = dh // 2
    idx = jnp.arange(dh, dtype=jnp.int32)
    freqs = 1.0 / (theta ** ((idx % half).astype(jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., T, Dh)
    sign = jnp.where(idx < half, -1.0, 1.0)
    return jnp.cos(ang)[..., None, :], (sign * jnp.sin(ang))[..., None, :]


def _rope_apply(x: jnp.ndarray, cos2: jnp.ndarray, sin2: jnp.ndarray):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([x[..., half:], x[..., :half]], axis=-1)
    return x * cos2 + rot * sin2


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, Dh); positions: (..., T) int32.

    Rotate-half form: the raw halves of ``x`` are concatenated *before* any
    arithmetic and the rotation runs on full-width (Dh) arrays, with the
    backward pass (a rotation by ``-theta``) spelled the same way via
    ``custom_vjp``.  The textbook ``concat(x1*cos - x2*sin, x2*cos + x1*sin)``
    -- compute on sliced halves, then concatenate -- is bit-identical in IEEE
    arithmetic but is miscompiled by the GSPMD partitioner when the head dim
    arrives sharded (e.g. wk sharded over 'model' propagates into Dh),
    silently producing wrong values; jax's auto-derived rope VJP contains the
    same unsafe pattern.  Only raw slices may feed a concatenate here.
    """
    cos2, sin2 = _rope_trig(positions, theta, x.shape[-1])
    return _rope_apply(x, cos2, sin2).astype(x.dtype)


def _rope_fwd(x, positions, theta):
    return rope(x, positions, theta), positions


def _rope_bwd(theta, positions, g):
    cos2, sin2 = _rope_trig(positions, theta, g.shape[-1])
    return (_rope_apply(g, cos2, -sin2).astype(g.dtype), None)


rope.defvjp(_rope_fwd, _rope_bwd)


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10_000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------- #
# Attention (GQA, qk-norm, sliding window, self/cross, KV cache)
# --------------------------------------------------------------------------- #

def attention_specs(cfg: ModelConfig, cross: bool = False) -> Dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s: Dict[str, Any] = {
        "wq": dense_spec(d, h * dh, axes=("embed", "heads")),
        "wk": dense_spec(d, kv * dh, axes=("embed", "kv_heads")),
        "wv": dense_spec(d, kv * dh, axes=("embed", "kv_heads")),
        "wo": dense_spec(h * dh, d, axes=("heads", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = {"scale": spec((dh,), (None,), init="ones")}
        s["k_norm"] = {"scale": spec((dh,), (None,), init="ones")}
    if cross:
        s["gate"] = spec((), (), init="zeros")    # llama-vision tanh gate
    return s


def init_kv_cache(batch: int, max_len: int, cfg: ModelConfig, dtype) -> Dict:
    kv, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, max_len, kv, dh), dtype),
        "v": jnp.zeros((batch, max_len, kv, dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _split_heads(x, n, dh):
    return x.reshape(x.shape[:-1] + (n, dh))


def attention(
    p: Dict,
    x: jnp.ndarray,                       # (B, T, D)
    cfg: ModelConfig,
    rt: Optional[Runtime] = None,
    *,
    positions: Optional[jnp.ndarray] = None,
    kv_x: Optional[jnp.ndarray] = None,   # cross-attention source (B, S, D)
    cache: Optional[Dict] = None,         # decode KV cache
    causal: bool = True,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Returns (out, updated_cache). Handles: training (full seq), prefill
    (full seq + cache fill), decode (T==1 + cache append), cross-attn."""
    b, t, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    cd = x.dtype

    q = _split_heads(dense(p["wq"], x, rt), h, dh)
    src = kv_x if kv_x is not None else x
    k = _split_heads(dense(p["wk"], src, rt), kv, dh)
    v = _split_heads(dense(p["wv"], src, rt), kv, dh)

    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)

    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    if kv_x is None and cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    q_pos = positions                                        # (B, T)
    if cache is not None and kv_x is None:
        start = cache["len"]
        w_cache = cache["k"].shape[1]
        circular = (cfg.swa_window is not None and w_cache <= cfg.swa_window)
        if circular and t >= w_cache:
            # Sliding-window prefill into a circular cache: keep the last
            # W tokens; token j lives at slot j % W (roll aligns them).
            shift = (t - w_cache) % w_cache
            ck = jnp.roll(k[:, -w_cache:], shift, axis=1).astype(cache["k"].dtype)
            cv = jnp.roll(v[:, -w_cache:], shift, axis=1).astype(cache["v"].dtype)
            cache = {"k": ck, "v": cv, "len": start + t}
            # In-pass attention uses the full-sequence k/v (window-masked).
            kv_pos = q_pos
            kv_valid = jnp.ones(k.shape[:2], bool)
        elif circular:
            # Decode (t small): write at slot len % W.
            slot = start % w_cache
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
            new_len = start + t
            cache = {"k": ck, "v": cv, "len": new_len}
            k, v = ck, cv
            # Slot s holds the latest token position == s (mod W), < len.
            s_idx = jnp.arange(w_cache, dtype=jnp.int32)
            tok_pos = new_len - 1 - ((new_len - 1 - s_idx) % w_cache)
            kv_pos = tok_pos[None, :]
            kv_valid = (tok_pos >= 0)[None, :]
        else:
            # Append current k/v at cache["len"].
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
            cache = {"k": ck, "v": cv, "len": start + t}
            k, v = ck, cv
            kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
            kv_valid = kv_pos < cache["len"]
    else:
        kv_pos = (jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
                  if kv_x is not None else q_pos)
        kv_valid = None        # fully valid; flash skips masks if non-causal

    # Grouped-query attention: (B, T, KV, G, Dh) vs (B, S, KV, Dh).
    g = h // kv
    qg = q.reshape(b, t, kv, g, dh)
    s_len = k.shape[1]
    is_causal = causal and kv_x is None
    if q_pos.ndim == 1:
        q_pos = q_pos[None, :]
    if kv_pos.ndim == 1:
        kv_pos = kv_pos[None, :]
    q_pos = jnp.broadcast_to(q_pos, (b, t))
    kv_pos = jnp.broadcast_to(kv_pos, (b, s_len))
    if kv_valid is not None:
        kv_valid = jnp.broadcast_to(kv_valid, (b, s_len))

    threshold = rt.flash_threshold if rt is not None else 512 * 512
    if t > 1 and t * s_len > threshold:
        from .flash import flash_attention
        out = flash_attention(
            qg, k, v, q_pos, kv_pos, kv_valid,
            causal=is_causal, window=cfg.swa_window,
            q_chunk=rt.q_chunk if rt else 1024,
            kv_chunk=rt.kv_chunk if rt else 1024,
            causal_skip=rt.causal_skip if rt else False)
    else:
        scale = dh ** -0.5
        f32 = (rt is not None and rt.attn_in_dtype == "f32")
        qin = (qg.astype(jnp.float32) if f32 else qg) * jnp.asarray(
            scale, jnp.float32 if f32 else qg.dtype)
        kin = k.astype(jnp.float32) if f32 else k
        # bf16 operands with fp32 MXU accumulation: no fp32 cache round-trip.
        logits = jnp.einsum("btkgd,bskd->bkgts", qin, kin,
                            preferred_element_type=jnp.float32)
        mask = (kv_valid[:, None, None, None, :] if kv_valid is not None
                else jnp.ones((b, 1, 1, 1, s_len), bool))
        if is_causal:
            cm = q_pos[:, None, None, :, None] >= kv_pos[:, None, None, None, :]
            mask = jnp.logical_and(mask, cm)
            if cfg.swa_window:
                wm = (q_pos[:, None, None, :, None]
                      - kv_pos[:, None, None, None, :]) < cfg.swa_window
                mask = jnp.logical_and(mask, wm)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        vin = v.astype(jnp.float32) if f32 else v
        out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(vin.dtype), vin,
                         preferred_element_type=jnp.float32).astype(cd)
    out = out.reshape(b, t, h * dh)
    out = dense(p["wo"], out, rt)
    if "gate" in p:                                          # gated cross-attn
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(cd) * out
    return out, cache


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #

def mlp_specs(cfg: ModelConfig) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.act == "silu_gated":
        return {
            "wg": dense_spec(d, f, axes=("embed", "mlp")),
            "wu": dense_spec(d, f, axes=("embed", "mlp")),
            "wd": dense_spec(f, d, axes=("mlp", "embed")),
        }
    return {
        "wu": dense_spec(d, f, axes=("embed", "mlp")),
        "wd": dense_spec(f, d, axes=("mlp", "embed")),
    }


def mlp(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
        rt: Optional[Runtime] = None) -> jnp.ndarray:
    if cfg.act == "silu_gated":
        return dense(p["wd"], jax.nn.silu(dense(p["wg"], x, rt))
                     * dense(p["wu"], x, rt), rt)
    u = dense(p["wu"], x, rt)
    if cfg.act == "sq_relu":
        u = jnp.square(jax.nn.relu(u))
    else:
        u = jax.nn.gelu(u)
    return dense(p["wd"], u, rt)


# --------------------------------------------------------------------------- #
# Embeddings + loss
# --------------------------------------------------------------------------- #

def embed_spec(vocab: int, d: int) -> ParamSpec:
    return spec((vocab, d), ("vocab", "embed"), init="embed", scale=0.02)


def unembed_spec(d: int, vocab: int) -> Dict:
    return dense_spec(d, vocab, axes=("embed", "vocab"))


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over positions with label >= 0 (negative labels are padding)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    wmask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * wmask) / jnp.maximum(jnp.sum(wmask), 1.0)
