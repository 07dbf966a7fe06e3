"""Composable model blocks (functional: explicit param pytrees, no framework).

Every block follows ``apply_<x>(params, x, cfg, ctx) -> (x, new_cache)`` where
``ctx`` carries mode/positions/memory/cache.  Caches make prefill/decode work
for every family: KV rings for attention (global cache = ring of size S,
local = ring of size window), recurrent states for RG-LRU / xLSTM.

Recurrent blocks (mLSTM / sLSTM) are implemented in their *exact* paper
recurrence via lax.scan -- the faithful form; RG-LRU uses an associative scan
(parallel).  See DESIGN.md for the chunked/Pallas variants on real hardware.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from . import act_ctx
from .config import ModelConfig


@dataclasses.dataclass
class Ctx:
    mode: str                      # "train" | "prefill" | "decode"
    pos: jax.Array | None = None   # (B, T) absolute positions
    memory: jax.Array | None = None  # (B, M, D) cross-attn source (stub frontend)
    cache: Any = None              # per-layer cache pytree (prefill/decode)


Init = jax.nn.initializers.normal(stddev=0.02)

# bf16 on the wire (SPerf lever): jnp's default matmul accumulates to f32, and
# XLA hoists that convert above the TP partial-sum all-reduce -- putting f32
# activations on the interconnect.  preferred_element_type=bf16 keeps the dot
# output (and therefore the collective) in bf16: 2x fewer collective bytes.
# MXU accumulation is still f32 internally; only the cross-shard reduction is
# bf16 (standard practice, cf. MaxText).  Toggle for ablation via env.
import os as _os
WIRE_BF16 = _os.environ.get("REPRO_WIRE_F32", "") == ""


def mm(x, w):
    if WIRE_BF16 and x.dtype == jnp.bfloat16 and w.dtype == jnp.bfloat16:
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16)
    return x @ w


def _dense(key, shape, dtype):
    return Init(key, shape, dtype)


def rmsnorm(scale, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(x.dtype)


def rope(x, pos, theta):
    """x: (B, T, H, hd), pos: (B, T) -> rotated."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs       # (B, T, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- attn
def init_attention(cfg: ModelConfig, key, cross: bool = False, dtype=jnp.bfloat16):
    d, hd, h, kv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 5)
    p = {
        "wq": _dense(ks[0], (d, h * hd), dtype),
        "wk": _dense(ks[1], (d, kv * hd), dtype),
        "wv": _dense(ks[2], (d, kv * hd), dtype),
        "wo": _dense(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


Q_CHUNK = 512  # memory-efficient attention: peak logits = B*H*Q_CHUNK*S


def _attend_dense(q, k, v, mask, cfg: ModelConfig):
    """q: (B,T,H,hd); k,v: (B,S,Kv,hd); mask: (B,T,S) or (T,S). GQA-grouped."""
    b, t, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    q = q.reshape(b, t, kv, g, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (cfg.hd ** -0.5)
    if cfg.attn_softcap is not None:
        logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap
    m = mask if mask.ndim == 3 else mask[None]
    logits = jnp.where(m[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, t, h * hd).astype(v.dtype)


def _attend(q, k, v, mask, cfg: ModelConfig):
    """Query-chunked attention: O(Q_CHUNK * S) logits live at once instead of
    O(T * S) -- the XLA-side training twin of kernels/flash_attention.py
    (autodiff-able under remat); the scan keeps HLO and dry-run memory small."""
    b, t, h, hd = q.shape
    if t <= Q_CHUNK or t % Q_CHUNK != 0:
        return _attend_dense(q, k, v, mask, cfg)
    nc = t // Q_CHUNK
    qs = jnp.moveaxis(q.reshape(b, nc, Q_CHUNK, h, hd), 1, 0)
    if mask.ndim == 3:
        ms = jnp.moveaxis(mask.reshape(b, nc, Q_CHUNK, -1), 1, 0)
    else:
        ms = mask.reshape(nc, Q_CHUNK, -1)
    # checkpoint the chunk so backward recomputes the (chunk x S) probs
    # instead of storing every chunk's softmax (flash-attention residuals)
    body = jax.checkpoint(
        lambda args: _attend_dense(args[0], k, v, args[1], cfg))
    out = jax.lax.map(body, (qs, ms))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * hd)


def apply_attention(p, x, cfg: ModelConfig, ctx: Ctx, *,
                    causal: bool = True, window: Optional[int] = None,
                    cross: bool = False):
    """Self- or cross-attention with ring caches for prefill/decode."""
    b, t, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = mm(x, p["wq"]).reshape(b, t, h, hd)
    if cross:
        mem = ctx.memory
        if ctx.cache is not None and "k" in ctx.cache and ctx.mode == "decode":
            k, v = ctx.cache["k"], ctx.cache["v"]
            new_cache = ctx.cache
        else:
            k = mm(mem, p["wk"]).reshape(b, -1, kv, hd)
            v = mm(mem, p["wv"]).reshape(b, -1, kv, hd)
            new_cache = {"k": k, "v": v}
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
            k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
        mask = jnp.ones((t, k.shape[1]), bool)
        out = _attend(q, k, v, mask, cfg)
        return x_out(p, out, b, t), new_cache

    k = mm(x, p["wk"]).reshape(b, t, kv, hd)
    v = mm(x, p["wv"]).reshape(b, t, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    pos = ctx.pos if ctx.pos is not None else \
        jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    if ctx.mode == "train" or ctx.cache is None or ctx.mode == "prefill":
        # batch-uniform positions in train/prefill -> a 2D (T,T) mask suffices
        ar = jnp.arange(t, dtype=jnp.int32)
        qp, kp = ar[:, None], ar[None, :]
        mask = jnp.ones((t, t), bool)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        out = x_out(p, _attend(q, k, v, mask, cfg), b, t)
        if ctx.mode != "prefill" or ctx.cache is None:
            return out, None
        # fill the ring with the last min(T, L) tokens for subsequent decode
        # (a ring cannot hold the full prefill when T > L; queries above
        #  already attended the exact windowed mask)
        cache = ctx.cache
        L = cache["k"].shape[1]
        tw = min(t, L)
        slots = pos[:, t - tw:] % L
        new_cache = {
            "k": _ring_write(cache["k"], k[:, t - tw:], slots),
            "v": _ring_write(cache["v"], v[:, t - tw:], slots),
            "pos": cache["pos"].at[jnp.arange(b)[:, None], slots].set(
                pos[:, t - tw:]),
        }
        return out, new_cache

    # decode: ring cache (B, L, Kv, hd) + cache positions (B, L)
    cache = ctx.cache
    L = cache["k"].shape[1]
    slots = pos % L                                          # (B, T)
    ck = _ring_write(cache["k"], k, slots)
    cv = _ring_write(cache["v"], v, slots)
    cpos = cache["pos"].at[jnp.arange(b)[:, None], slots].set(pos)
    new_cache = {"k": ck, "v": cv, "pos": cpos}
    qp = pos[:, :, None]
    kp = cpos[:, None, :]                                    # (B,1,L)
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return x_out(p, _attend(q, ck, cv, mask, cfg), b, t), new_cache


def _ring_write(buf, vals, slots):
    """buf: (B, L, ...), vals: (B, T, ...), slots: (B, T) -> scattered buf."""
    b = buf.shape[0]
    bi = jnp.arange(b)[:, None]
    return buf.at[bi, slots].set(vals.astype(buf.dtype))


def x_out(p, attn_out, b, t):
    return mm(attn_out, p["wo"])


def init_attention_cache(cfg: ModelConfig, batch: int, length: int,
                         dtype=jnp.bfloat16):
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {"k": jnp.zeros((batch, length, kv, hd), dtype),
            "v": jnp.zeros((batch, length, kv, hd), dtype),
            "pos": jnp.full((batch, length), -1, jnp.int32)}


# ---------------------------------------------------------------------- ffn
def init_mlp(cfg: ModelConfig, key, dtype=jnp.bfloat16, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {"wi": _dense(ks[0], (d, f), dtype),
            "wg": _dense(ks[1], (d, f), dtype),
            "wo": _dense(ks[2], (f, d), dtype)}


def apply_mlp(p, x):
    return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"]), p["wo"])


# ---------------------------------------------------------------------- moe
def init_moe(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_experts
    ks = jax.random.split(key, 5)
    p = {"router": _dense(ks[0], (d, e), jnp.float32),
         "wi": _dense(ks[1], (e, d, f), dtype),
         "wg": _dense(ks[2], (e, d, f), dtype),
         "wo": _dense(ks[3], (e, f, d), dtype)}
    if m.dense_residual:
        p["dense"] = init_mlp(cfg, ks[4], dtype)
    return p


def apply_moe(p, x, cfg: ModelConfig):
    """Top-k MoE FFN.  Two implementations:

    * shard_map expert-parallel path (mesh context installed, experts divide
      `model`): every model-rank owns E/tp experts, activations stay
      replicated over `model` (they already are under 2D sharding), each rank
      gathers only its own experts' weights over `data` (ZeRO-style, ~param
      bytes), buckets its local tokens for its own experts, runs the dense
      expert einsum locally, and one psum over `model` combines.  Collectives
      per layer = weight gather + one (B_loc, S, D) all-reduce -- the XLA
      global-scatter path replicates (E, C, D) dispatch buffers and
      all-reduces them (measured ~50x more bytes on qwen3-moe;
      EXPERIMENTS.md SPerf cell A).
    * pure-XLA fallback (single-device tests, eager use, tiny meshes).
    """
    mesh = act_ctx.mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and cfg.moe.n_experts % mesh.shape["model"] == 0
            and x.shape[0] % max(act_ctx.dp_size(), 1) == 0
            # decode (T==1): the per-step ZeRO weight gather would dwarf the
            # few active tokens -- GSPMD's dispatch wins there (measured:
            # arctic decode 0.04s vs 2.7s collective under EP)
            and x.shape[1] > 1):
        return _apply_moe_shardmap(p, x, cfg, mesh)
    return _apply_moe_xla(p, x, cfg)


def _bucket_and_run(xt, w, ids, wi, wg, wo, n_buckets, cap, bucket_of, dtype):
    """Slot assignments into (n_buckets, cap), run experts, combine back.
    bucket_of >= n_buckets marks an assignment as not-ours/dropped."""
    tk = ids.size
    k = ids.shape[-1]
    d = xt.shape[-1]
    flat_b = bucket_of.reshape(-1)
    order = jnp.argsort(flat_b, stable=True)
    sorted_b = flat_b[order]
    grp = (jnp.arange(tk, dtype=jnp.int32)
           - jnp.searchsorted(sorted_b, sorted_b, side="left").astype(jnp.int32))
    keep = (sorted_b < n_buckets) & (grp < cap)
    slot = jnp.where(keep, sorted_b * cap + grp, n_buckets * cap)
    tok = order // k
    buf = jnp.zeros((n_buckets * cap + 1, d), dtype).at[slot].set(
        jnp.where(keep[:, None], xt[tok], 0))
    xe = buf[: n_buckets * cap].reshape(n_buckets, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
        jnp.einsum("ecd,edf->ecf", xe, wi)
    ye = jnp.einsum("ecf,efd->ecd", h, wo).reshape(n_buckets * cap, d)
    back = jnp.where(keep[:, None],
                     ye[jnp.minimum(slot, n_buckets * cap - 1)], 0)
    w_sorted = w.reshape(-1)[order].astype(dtype)
    return jnp.zeros((xt.shape[0], d), dtype).at[tok].add(
        back * w_sorted[:, None])


def _apply_moe_shardmap(p, x, cfg: ModelConfig, mesh):
    m = cfg.moe
    b, s, d = x.shape
    dp = act_ctx.dp_axes()
    dp_size = max(act_ctx.dp_size(), 1)
    tp = mesh.shape["model"]
    e, k = m.n_experts, m.top_k
    e_loc = e // tp
    t_loc = (b // dp_size) * s
    cap = max(1, int(math.ceil(t_loc * k / e * m.capacity_factor)))

    x_spec = P(dp if dp else None, None, None)
    specs_in = [P("model", "data", None), P("model", "data", None),
                P("model", None, "data"), P("data", None), x_spec]

    def body(wi, wg, wo, router, x_loc):
        mi = jax.lax.axis_index("model")
        # ZeRO gather of this rank's expert weights over `data`
        wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        router_f = jax.lax.all_gather(router, "data", axis=0, tiled=True)
        xt = x_loc.reshape(-1, d)
        probs = jax.nn.softmax(xt.astype(jnp.float32) @ router_f, axis=-1)
        w, ids = jax.lax.top_k(probs, k)                   # (t_loc, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        # assignments owned by this model-rank; others -> bucket e_loc (drop)
        local_e = ids - mi * e_loc
        bucket_of = jnp.where((local_e >= 0) & (local_e < e_loc),
                              local_e, e_loc)
        out = _bucket_and_run(xt, w, ids, wi, wg, wo, e_loc, cap,
                              bucket_of, x.dtype)
        out = jax.lax.psum(out, "model")
        return out.reshape(x_loc.shape)

    args = [p["wi"], p["wg"], p["wo"], p["router"].astype(x.dtype), x]
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs_in),
                       out_specs=x_spec, check_vma=False)
    out = fn(*args)
    if m.dense_residual:
        # dense residual OUTSIDE shard_map: GSPMD shards it once (computing
        # it per model-rank would 16x its FLOPs -- measured on arctic)
        out = out + apply_mlp(p["dense"], x)
    return out


def _apply_moe_xla(p, x, cfg: ModelConfig):
    """Sort-based top-k dispatch with static per-expert capacity (token-drop).

    FLOPs = T * top_k * capacity_factor * 3 * D * F * 2 (active params only)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32)) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)                   # (T, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    e, k = m.n_experts, m.top_k
    cap = max(1, int(math.ceil(t * k / e * m.capacity_factor)))
    out = _bucket_and_run(xt, w, ids, p["wi"], p["wg"], p["wo"], e, cap,
                          ids, x.dtype)
    if m.dense_residual:
        out = out + apply_mlp(p["dense"], xt)
    return out.reshape(b, s, d)


# -------------------------------------------------------------------- rglru
def init_rglru(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    d = cfg.d_model
    w = int(cfg.rglru_expand * d)
    ks = jax.random.split(key, 7)
    return {"wx": _dense(ks[0], (d, w), dtype),
            "wy": _dense(ks[1], (d, w), dtype),      # gate branch
            "conv": _dense(ks[2], (cfg.conv_width, w), dtype),
            "a_log": jnp.full((w,), 0.5, jnp.float32),
            "wgx": _dense(ks[3], (w, w), dtype),     # input gate
            "wga": _dense(ks[4], (w, w), dtype),     # recurrence gate
            "wo": _dense(ks[5], (w, d), dtype)}


def _linear_scan_impl(u, a, reverse=False):
    def combine(x, y):
        a1, u1 = x
        a2, u2 = y
        return a1 * a2, a2 * u1 + u2
    _, h = jax.lax.associative_scan(combine, (a, u), axis=1, reverse=reverse)
    return h


@jax.custom_vjp
def _rglru_scan(u, a):
    """h_t = a_t * h_{t-1} + u_t via associative scan.  u, a: (B, T, W) f32.

    Custom VJP: naive autodiff of associative_scan keeps O(log T) full-width
    intermediates live; the adjoint of a linear recurrence is just the same
    recurrence run backwards (g_t = dh_t + a_{t+1} g_{t+1}), so the backward
    pass costs one more scan and the residuals are exactly (a, h)."""
    return _linear_scan_impl(u, a)


def _rglru_scan_fwd(u, a):
    h = _linear_scan_impl(u, a)
    return h, (a, h)


def _rglru_scan_bwd(res, g):
    a, h = res
    a_next = jnp.concatenate([a[:, 1:], jnp.ones_like(a[:, :1])], axis=1)
    gacc = _linear_scan_impl(g, a_next, reverse=True)
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)
    return gacc, gacc * h_prev


_rglru_scan.defvjp(_rglru_scan_fwd, _rglru_scan_bwd)


def apply_rglru(p, x, cfg: ModelConfig, ctx: Ctx):
    """RecurrentGemma recurrent block: proj -> causal conv -> RG-LRU -> gate."""
    b, t, d = x.shape
    u = x @ p["wx"]                                          # (B,T,W)
    gate = jax.nn.gelu(x @ p["wy"])
    cache = ctx.cache or {}
    cw = cfg.conv_width
    if ctx.mode == "decode" and "conv" in cache:
        hist = jnp.concatenate([cache["conv"], u], axis=1)   # (B, cw-1+T, W)
    else:
        hist = jnp.pad(u, ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(hist[:, i: i + t] * p["conv"][i][None, None]
               for i in range(cw))
    ga = jax.nn.sigmoid(conv @ p["wga"])
    gx = jax.nn.sigmoid(conv @ p["wgx"])
    c = 8.0
    log_a = (-c * jax.nn.softplus(p["a_log"])[None, None]
             * ga.astype(jnp.float32))
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.clip(1.0 - a ** 2, 1e-12, None))
    un = (gx * conv).astype(jnp.float32) * mult
    if ctx.mode == "decode" and "h" in cache:
        h0 = cache["h"]
        h = a[:, 0] * h0 + un[:, 0]
        hs = h[:, None]
    else:
        hs = _rglru_scan(un, a)
        h = hs[:, -1]
    new_cache = {"conv": hist[:, -(cw - 1):] if cw > 1 else hist[:, :0],
                 "h": h} if ctx.mode != "train" else None
    y = (hs.astype(x.dtype) * gate) @ p["wo"]
    return y, new_cache


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    w = int(cfg.rglru_expand * cfg.d_model)
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
            "h": jnp.zeros((batch, w), jnp.float32)}


# -------------------------------------------------------------------- xlstm
def init_mlstm(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    d = cfg.d_model
    w = int(cfg.mlstm_expand * d)
    ks = jax.random.split(key, 8)
    return {"wu": _dense(ks[0], (d, w), dtype),
            "wg": _dense(ks[1], (d, w), dtype),
            "wq": _dense(ks[2], (w, w), dtype),
            "wk": _dense(ks[3], (w, w), dtype),
            "wv": _dense(ks[4], (w, w), dtype),
            "wi": _dense(ks[5], (w, cfg.n_heads), dtype),
            "wf": _dense(ks[6], (w, cfg.n_heads), dtype),
            "wo": _dense(ks[7], (w, d), dtype)}


def _mlstm_sequential(q, k, v, log_i, log_f, c0, n0, m0):
    """Exact stabilized recurrence (decode path + chunkwise test oracle).
    q,k,v: (B,T,H,hd) f32; log_i/log_f: (B,T,H) f32."""

    def step(carry, inp):
        c, n, m = carry
        qt, kt, vt, li, lf = inp
        m_new = jnp.maximum(lf + m, li)
        f_ = jnp.exp(lf + m - m_new)[..., None]              # (B,H,1)
        i_ = jnp.exp(li - m_new)[..., None]
        n = f_ * n + i_ * kt
        c = f_[..., None] * c + i_[..., None] * (vt[..., :, None] *
                                                 kt[..., None, :])
        num = jnp.einsum("bhij,bhj->bhi", c, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, qt)), 1.0)
        return (c, n, m_new), num / den[..., None]

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_i, log_f))
    (cT, nT, mT), hs = jax.lax.scan(step, (c0, n0, m0), xs)
    return jnp.moveaxis(hs, 0, 1), (cT, nT, mT)


def _mlstm_chunk(carry, inp):
    """One chunk of the stabilized chunkwise-parallel mLSTM (the form real
    kernels use: BPTT stores O(T/L) inter-chunk states, not O(T) matrices).

    q,k,v: (B,H,L,hd) f32; log_i/log_f: (B,H,L) f32; carry (C, n, m)."""
    c_in, n_in, m_in = carry
    q, k, v, log_i, log_f = inp
    L = q.shape[2]
    b_cum = jnp.cumsum(log_f, axis=-1)                       # inclusive decay
    # intra-chunk pairwise log-weights: b_t - b_j + log_i_j for j <= t
    dmat = (b_cum[..., :, None] - b_cum[..., None, :] + log_i[..., None, :])
    causal = jnp.tril(jnp.ones((L, L), bool))
    dmat = jnp.where(causal, dmat, -jnp.inf)
    m_intra = jnp.max(dmat, axis=-1)                         # (B,H,L)
    m_inter = m_in[..., None] + b_cum                        # (B,H,L)
    m_t = jnp.maximum(m_inter, m_intra)
    d = jnp.exp(dmat - m_t[..., None])                       # (B,H,L,L)
    r = jnp.exp(m_inter - m_t)                               # (B,H,L)
    scores = jnp.einsum("bhtd,bhjd->bhtj", q, k) * d
    num = (jnp.einsum("bhtj,bhjd->bhtd", scores, v)
           + r[..., None] * jnp.einsum("bhij,bhtj->bhti", c_in, q))
    den = (jnp.sum(scores, axis=-1)
           + r * jnp.einsum("bhj,bhtj->bht", n_in, q))
    h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    # chunk-exit state
    B_L = b_cum[..., -1]
    m_out = jnp.maximum(m_in + B_L,
                        jnp.max(B_L[..., None] - b_cum + log_i, axis=-1))
    w = jnp.exp(B_L[..., None] - b_cum + log_i - m_out[..., None])  # (B,H,L)
    decay = jnp.exp(m_in + B_L - m_out)
    c_out = (decay[..., None, None] * c_in
             + jnp.einsum("bhj,bhjv,bhjk->bhvk", w, v, k))
    n_out = decay[..., None] * n_in + jnp.einsum("bhj,bhjk->bhk", w, k)
    return (c_out, n_out, m_out), h


def apply_mlstm(p, x, cfg: ModelConfig, ctx: Ctx):
    """mLSTM (xLSTM Sec. 2.3): chunkwise-parallel stabilized form for
    train/prefill (chunk = cfg.mlstm_chunk), exact recurrence for decode.
    tests/test_xlstm_forms.py asserts the two forms agree.

    State per head: C (hd,hd) matrix memory, n (hd,), m () stabilizer."""
    b, t, d = x.shape
    h = cfg.n_heads
    u = x @ p["wu"]
    gate = jax.nn.silu(x @ p["wg"])
    w = u.shape[-1]
    hd = w // h
    q = (u @ p["wq"]).reshape(b, t, h, hd).astype(jnp.float32)
    k = ((u @ p["wk"]) / math.sqrt(hd)).reshape(b, t, h, hd).astype(jnp.float32)
    v = (u @ p["wv"]).reshape(b, t, h, hd).astype(jnp.float32)
    log_i = jnp.clip(u @ p["wi"], -10.0, 10.0).astype(jnp.float32)   # (B,T,H)
    log_f = jax.nn.log_sigmoid((u @ p["wf"]).astype(jnp.float32))

    cache = ctx.cache or {}
    if "C" in cache:
        c0, n0, m0 = cache["C"], cache["n"], cache["m"]
    else:
        c0 = jnp.zeros((b, h, hd, hd), jnp.float32)
        n0 = jnp.zeros((b, h, hd), jnp.float32)
        m0 = jnp.full((b, h), -jnp.inf, jnp.float32)

    L = cfg.mlstm_chunk
    if t == 1 or (ctx.mode == "decode"):
        hs, (cT, nT, mT) = _mlstm_sequential(q, k, v, log_i, log_f, c0, n0, m0)
    else:
        # pad T to a chunk multiple; padded steps get log_i=-inf (no effect)
        tp = (t + L - 1) // L * L
        pad = tp - t
        def padt(a, fill=0.0):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                           constant_values=fill)
        qh = jnp.moveaxis(padt(q), 2, 1)                     # (B,H,Tp,hd)
        kh = jnp.moveaxis(padt(k), 2, 1)
        vh = jnp.moveaxis(padt(v), 2, 1)
        lih = jnp.moveaxis(padt(log_i, -jnp.inf), 2, 1)      # (B,H,Tp)
        lfh = jnp.moveaxis(padt(log_f), 2, 1)
        nch = tp // L
        split = lambda a: jnp.moveaxis(
            a.reshape(a.shape[0], a.shape[1], nch, L, *a.shape[3:]), 2, 0)
        xs = (split(qh), split(kh), split(vh), split(lih), split(lfh))
        chunk_body = jax.checkpoint(
            _mlstm_chunk, policy=jax.checkpoint_policies.nothing_saveable)
        (cT, nT, mT), hs_c = jax.lax.scan(chunk_body, (c0, n0, m0), xs)
        # (nch,B,H,L,hd) -> (B,H,Tp,hd) -> (B,T,H,hd)
        hs = jnp.moveaxis(jnp.moveaxis(hs_c, 0, 2).reshape(b, h, tp, hd),
                          1, 2)[:, :t]
    out = hs.reshape(b, t, w).astype(x.dtype)
    new_cache = ({"C": cT, "n": nT, "m": mT} if ctx.mode != "train" else None)
    return (out * gate) @ p["wo"], new_cache


def init_mlstm_cache(cfg: ModelConfig, batch: int):
    w = int(cfg.mlstm_expand * cfg.d_model)
    hd = w // cfg.n_heads
    return {"C": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, cfg.n_heads, hd), jnp.float32),
            "m": jnp.full((batch, cfg.n_heads), -jnp.inf, jnp.float32)}


def init_slstm(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    d = cfg.d_model
    f = int(cfg.slstm_proj * d)
    ks = jax.random.split(key, 6)
    return {"wz": _dense(ks[0], (d, d), dtype),
            "wi": _dense(ks[1], (d, d), dtype),
            "wf": _dense(ks[2], (d, d), dtype),
            "wo": _dense(ks[3], (d, d), dtype),
            "up": _dense(ks[4], (d, f), dtype),
            "down": _dense(ks[5], (f, d), dtype)}


def apply_slstm(p, x, cfg: ModelConfig, ctx: Ctx):
    """sLSTM (xLSTM Sec. 2.2): scalar memory, exp input gating, stabilized."""
    b, t, d = x.shape
    z = jnp.tanh(x @ p["wz"]).astype(jnp.float32)
    log_i = jnp.clip(x @ p["wi"], -10, 10).astype(jnp.float32)
    log_f = jax.nn.log_sigmoid((x @ p["wf"]).astype(jnp.float32))
    o = jax.nn.sigmoid(x @ p["wo"]).astype(jnp.float32)

    cache = ctx.cache or {}
    if "c" in cache:
        c0, n0, m0 = cache["c"], cache["n"], cache["m"]
    else:
        c0 = jnp.zeros((b, d), jnp.float32)
        n0 = jnp.zeros((b, d), jnp.float32)
        m0 = jnp.full((b, d), -jnp.inf, jnp.float32)

    def step(carry, inp):
        c, n, m = carry
        zt, li, lf, ot = inp
        m_new = jnp.maximum(lf + m, li)
        f_ = jnp.exp(lf + m - m_new)
        i_ = jnp.exp(li - m_new)
        c = f_ * c + i_ * zt
        n = f_ * n + i_
        h = ot * c / jnp.maximum(n, 1.0)
        return (c, n, m_new), h

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (z, log_i, log_f, o))
    (cT, nT, mT), hs = jax.lax.scan(step, (c0, n0, m0), xs)
    out = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    new_cache = ({"c": cT, "n": nT, "m": mT} if ctx.mode != "train" else None)
    y = out @ p["up"]
    return jax.nn.gelu(y) @ p["down"], new_cache


def init_slstm_cache(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    z = lambda: jnp.zeros((batch, d), jnp.float32)
    return {"c": z(), "n": z(), "m": jnp.full((batch, d), -jnp.inf, jnp.float32)}
