"""Shared neural-net layers (functional style, params as pytrees).

Covers every attention/MLP/norm/rotary variant the assigned architectures
need: GQA with grouped einsums (no materialized KV repeat), sliding-window +
global alternation (gemma2), logit softcapping, RoPE in full / half
(chatglm3) / M-RoPE (qwen2-vl) modes, SwiGLU/GeGLU/GELU MLPs, RMS/LayerNorm.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel import axis_size, constrain

from .config import ModelConfig

Params = dict[str, Any]


def _gqa_model_axes(KV: int, G: int) -> tuple[str | None, str | None]:
    """Which of the grouped-head axes (KV, G) carries the "model" mesh axis.

    Prefer sharding KV heads (keeps the KV cache sharded); fall back to the
    group axis when KV is too small (e.g. kv=2 under TP=16 -- the paper-pool
    GQA norm), replicating K/V but keeping Q-head compute sharded.
    """
    tp = axis_size("model")
    if tp > 1 and KV % tp == 0:
        return "model", None
    if tp > 1 and G % tp == 0:
        return None, "model"
    return None, None


# ------------------------------- init utils -------------------------------

def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    scale = (1.0 / fan_in) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def norm_init(d: int, cfg: ModelConfig) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# --------------------------------- rotary ---------------------------------

def rope_angles(cfg: ModelConfig, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables.

    positions: (B, S) int32 -- or (3, B, S) for M-RoPE (t/h/w coordinates).
    Returns cos/sin of shape (B, S, rot_dim // 2) (f32).
    """
    hd = cfg.head_dim
    if cfg.rope_mode == "none":
        raise ValueError("no rope")
    if cfg.rope_mode == "half":
        rot = hd // 2
    else:
        rot = hd
    half = rot // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)

    if cfg.rope_mode == "mrope":
        if positions.ndim == 2:  # text-only: t == h == w
            positions = jnp.broadcast_to(positions[None], (3, *positions.shape))
        sec = cfg.mrope_sections  # sums to half
        ang_3 = positions[..., None].astype(jnp.float32) * freqs  # (3, B, S, half)
        parts = []
        start = 0
        for i, s in enumerate(sec):
            parts.append(ang_3[i, :, :, start:start + s])
            start += s
        ang = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, n, head_dim).  Rotate first rot dims (half mode: hd//2)."""
    hd = x.shape[-1]
    rot = hd // 2 if cfg.rope_mode == "half" else hd
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    rotated = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rotated, xp], axis=-1) if rot < hd else rotated


# -------------------------------- attention --------------------------------

def _pad_heads(w: jax.Array, axis: int, n_eff: int) -> jax.Array:
    """Zero-pad the head axis up to ``n_eff`` (exact math: padded heads have
    zero projections in AND out, so they contribute nothing)."""
    n = w.shape[axis]
    if n == n_eff:
        return w
    pad = [(0, 0)] * w.ndim
    pad[axis] = (0, n_eff - n)
    return jnp.pad(w, pad)


def attn_init(key, cfg: ModelConfig, d: int | None = None) -> Params:
    d = d or cfg.d_model
    hd, H, KV = cfg.head_dim, cfg.n_heads_eff, cfg.n_kv_heads_eff
    nH, nKV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wq": _pad_heads(_dense_init(ks[0], (d, nH, hd), dt, d), 1, H),
        "wk": _pad_heads(_dense_init(ks[1], (d, nKV, hd), dt, d), 1, KV),
        "wv": _pad_heads(_dense_init(ks[2], (d, nKV, hd), dt, d), 1, KV),
        "wo": _pad_heads(_dense_init(ks[3], (nH, hd, d), dt, nH * hd), 0, H),
    }


_NEG = jnp.float32(-1e30)


def _mask_chunk(qpos, kpos, window, kv_len_mask_chunk):
    """(qc, 1) x (1, kc) -> bool mask; window may be a traced int32.

    qpos may carry a leading batch dim (B, qc, 1) when the decode batch has
    per-row cursors (continuous batching); the mask then resolves per row.
    """
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if mask.ndim == 3:                                      # per-row cursors
        mask = mask[:, None, None]                          # (B,1,1,qc,kc)
    else:
        mask = mask[None, None, None]                       # (1,1,1,qc,kc)
    if kv_len_mask_chunk is not None:
        mask = mask & kv_len_mask_chunk[:, None, None, None, :]
    return mask


def _attn_plain(q, k, v, *, causal_offset, window, softcap, kv_len_mask):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kv_ax, g_ax = _gqa_model_axes(KV, G)
    qg = q.reshape(B, Sq, KV, G, hd)
    qg = constrain(qg, "batch", None, kv_ax, g_ax, None)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    scores = constrain(scores, "batch", kv_ax, g_ax, None, None)
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    if jnp.ndim(causal_offset) == 1:
        # per-row decode cursors (continuous batching): offset (B,)
        qpos = (jnp.asarray(causal_offset, jnp.int32)[:, None, None]
                + jnp.arange(Sq)[None, :, None])            # (B, Sq, 1)
        kpos = jnp.arange(k.shape[1])[None, None, :]        # (1, 1, Sk)
    else:
        qpos = jnp.arange(Sq)[:, None] + causal_offset      # (Sq, 1) key-space pos
        kpos = jnp.arange(k.shape[1])[None, :]              # (1, Sk)
    mask = _mask_chunk(qpos, kpos, window, kv_len_mask)
    scores = jnp.where(mask, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def _flash_fwd_blocks(q, k, v, *, causal_offset, window, softcap, kv_len_mask,
                      q_chunk, kv_chunk, with_stats: bool):
    """Forward flash pass.  Returns (out, (m, logl)) per q position when
    ``with_stats`` (needed by the chunk-recompute backward)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    kv_ax, g_ax = _gqa_model_axes(KV, G)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = hd ** -0.5
    qg = q.reshape(B, nq, q_chunk, KV, G, hd)
    qg = constrain(qg, "batch", None, None, kv_ax, g_ax, None)
    kc = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, hd), 1, 0)   # (nk,B,kc,KV,hd)
    vc = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    kc = constrain(kc, None, "batch", None, kv_ax, None)
    vc = constrain(vc, None, "batch", None, kv_ax, None)
    lm = (
        None if kv_len_mask is None
        else jnp.moveaxis(kv_len_mask.reshape(B, nk, kv_chunk), 1, 0)
    )

    def q_block(qi, qblk):
        qblk = constrain(qblk, "batch", None, kv_ax, g_ax, None)
        qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None] + causal_offset

        def kv_step(carry, xs):
            m, l, acc = carry
            ki, kb, vb, lmb = xs
            s = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kb).astype(jnp.float32)
            s = constrain(s, "batch", kv_ax, g_ax, None, None)
            s = s * scale
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = _mask_chunk(qpos, kpos, window, lmb)
            s = jnp.where(mask, s, _NEG)
            new_m = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask, jnp.exp(s - new_m[..., None]), 0.0)
            corr = jnp.exp(m - new_m)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), vb)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            acc = constrain(acc, "batch", kv_ax, g_ax, None, None)
            return (new_m, l, acc), None

        m0 = jnp.full((B, KV, G, q_chunk), _NEG, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        if lm is None:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, x: kv_step(c, (*x, None)), (m0, l0, a0),
                (jnp.arange(nk), kc, vc))
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc, lm))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        out = jnp.einsum("bkgqh->bqkgh", out)                 # (B,qc,KV,G,hd)
        if with_stats:
            # logsumexp per q position: lse = m + log l
            lse = m + jnp.log(l_safe)                         # (B,KV,G,qc)
            return out, lse
        return out, jnp.zeros((), jnp.float32)

    outs, lses = jax.lax.map(
        lambda ix: q_block(ix[0], ix[1]),
        (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)),
    )                                                         # (nq,B,qc,KV,G,hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    return out, lses


def _flash_bwd(res, do, *, causal_offset, window, softcap, kv_len_mask,
               q_chunk, kv_chunk):
    """Chunk-recompute flash backward (FlashAttention-2 style).

    Saves only (q, k, v, out, lse); attention probabilities are recomputed
    per (q-chunk x kv-chunk) tile, so backward peak memory is
    O(q_chunk * kv_chunk), not O(Sq * Sk).
    """
    q, k, v, out, lses = res
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    kv_ax, g_ax = _gqa_model_axes(KV, G)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = hd ** -0.5
    qg = jnp.moveaxis(q.reshape(B, nq, q_chunk, KV, G, hd), 1, 0)
    og = jnp.moveaxis(out.reshape(B, nq, q_chunk, KV, G, hd), 1, 0)
    dog = jnp.moveaxis(
        do.reshape(B, nq, q_chunk, KV, G, hd), 1, 0).astype(jnp.float32)
    kc = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    lm = (None if kv_len_mask is None
          else jnp.moveaxis(kv_len_mask.reshape(B, nk, kv_chunk), 1, 0))
    # D_i = sum_h do_i * out_i  (per q position)
    Dg = jnp.einsum("nbqkgh,nbqkgh->nbkgq", dog,
                    og.astype(jnp.float32))                   # (nq,B,KV,G,qc)

    def q_pass(carry, xs):
        dk_acc, dv_acc = carry
        qi, qblk, dob, lseb, Db = xs
        dob = jnp.transpose(dob, (0, 2, 3, 1, 4))   # -> (B, KV, G, qc, hd)
        qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None] + causal_offset

        def kv_step(dq_c, xs2):
            ki, kb, vb, lmb = xs2
            s_raw = jnp.einsum(
                "bqkgh,bskh->bkgqs", qblk, kb).astype(jnp.float32) * scale
            if softcap is not None:
                t = jnp.tanh(s_raw / softcap)
                s = t * softcap
            else:
                s = s_raw
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = _mask_chunk(qpos, kpos, window, lmb)
            p = jnp.where(mask, jnp.exp(s - lseb[..., None]), 0.0)
            # dv tile
            dv_t = jnp.einsum("bkgqs,bkgqh->bskh", p, dob)
            # dp, ds
            dp = jnp.einsum("bkgqh,bskh->bkgqs", dob, vb.astype(jnp.float32))
            ds = p * (dp - Db[..., None])
            if softcap is not None:
                ds = ds * (1.0 - jnp.square(t))               # d tanh
            ds = jnp.where(mask, ds, 0.0) * scale
            dq_t = jnp.einsum("bkgqs,bskh->bqkgh", ds, kb.astype(jnp.float32))
            dk_t = jnp.einsum("bkgqs,bqkgh->bskh", ds, qblk.astype(jnp.float32))
            return dq_c + dq_t, (dk_t, dv_t)

        dq0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        if lm is None:
            dq_b, (dk_t, dv_t) = jax.lax.scan(
                lambda c, x: kv_step(c, (*x, None)), dq0,
                (jnp.arange(nk), kc, vc))
        else:
            dq_b, (dk_t, dv_t) = jax.lax.scan(
                kv_step, dq0, (jnp.arange(nk), kc, vc, lm))
        return (dk_acc + dk_t, dv_acc + dv_t), dq_b

    dk0 = jnp.zeros((nk, B, kv_chunk, KV, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, kv_chunk, KV, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_pass, (dk0, dv0), (jnp.arange(nq), qg, dog, lses, Dg))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, Sk, KV, hd).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, Sk, KV, hd).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attn_flash_cvjp(q, k, v, window_f, causal_offset, softcap,
                     q_chunk, kv_chunk):
    out, _ = _flash_fwd_blocks(
        q, k, v, causal_offset=causal_offset,
        window=window_f.astype(jnp.int32), softcap=softcap,
        kv_len_mask=None, q_chunk=q_chunk, kv_chunk=kv_chunk, with_stats=False)
    return out


def _cvjp_fwd(q, k, v, window_f, causal_offset, softcap, q_chunk, kv_chunk):
    out, lses = _flash_fwd_blocks(
        q, k, v, causal_offset=causal_offset,
        window=window_f.astype(jnp.int32), softcap=softcap,
        kv_len_mask=None, q_chunk=q_chunk, kv_chunk=kv_chunk, with_stats=True)
    return out, (q, k, v, out, lses, window_f)


def _cvjp_bwd(causal_offset, softcap, q_chunk, kv_chunk, res, do):
    q, k, v, out, lses, window_f = res
    dq, dk, dv = _flash_bwd(
        (q, k, v, out, lses), do, causal_offset=causal_offset,
        window=window_f.astype(jnp.int32), softcap=softcap, kv_len_mask=None,
        q_chunk=q_chunk, kv_chunk=kv_chunk)
    return dq, dk, dv, jnp.zeros_like(window_f)


_attn_flash_cvjp.defvjp(_cvjp_fwd, _cvjp_bwd)


def _attn_flash(q, k, v, *, causal_offset, window, softcap, kv_len_mask,
                q_chunk, kv_chunk):
    """Online-softmax (flash-style) attention, chunked over Sq and Sk.

    Pure jnp + lax.scan; HLO stays O(1) in sequence length.  When there is
    no kv_len_mask (the training path -- window may be a traced per-layer
    scalar), routes through the custom-VJP variant whose backward
    recomputes probabilities per tile (peak O(q_chunk x kv_chunk) instead
    of O(Sq x Sk) residuals -- 6.4 GB/layer saved for mistral train_4k).
    """
    if kv_len_mask is None and (isinstance(causal_offset, int)
                                or causal_offset is None):
        wf = jnp.asarray(window if window is not None else (1 << 30),
                         jnp.float32)
        return _attn_flash_cvjp(q, k, v, wf, int(causal_offset or 0),
                                softcap, q_chunk, kv_chunk)
    out, _ = _flash_fwd_blocks(
        q, k, v, causal_offset=causal_offset, window=window, softcap=softcap,
        kv_len_mask=kv_len_mask, q_chunk=q_chunk, kv_chunk=kv_chunk,
        with_stats=False)
    return out


def _attn_decode_splitk(q, k, v, *, causal_offset, window, softcap,
                        kv_len_mask, seq_axes: tuple[str, ...]):
    """Split-K decode attention over a sequence-sharded KV cache.

    Flash-decoding on the mesh: each rank computes partial attention over
    its local S-chunk of the cache, then the softmax is reconciled with a
    pmax + two psums over ``seq_axes`` (a few KB of wire traffic) -- versus
    XLA's auto-SPMD fallback, which all-gathers the entire cache in fp32
    per layer (observed: 268 MB x 2 x n_layers per decoded token).

    ``causal_offset`` may be a scalar (uniform decode) or a (B,) vector of
    per-row cache cursors (continuous batching).  The vector offset is
    sharded exactly like q's batch axis and each rank resolves its rows'
    causal/window masks against its own key-position range -- every
    K-shard sees the same per-row validity rule, so the pmax/psum softmax
    reconciliation is row-independent and the batched result matches a
    solo decode of each row bitwise.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    bat = tuple(a for a in ("pod", "data")
                if a in mesh.axis_names and a not in seq_axes)
    b_entry = bat if (bat and B % _mesh_prod(mesh, bat) == 0) else None
    n_chunks = _mesh_prod(mesh, seq_axes)
    s_loc = Sk // n_chunks

    off = jnp.asarray(causal_offset, jnp.int32)
    per_row = off.ndim == 1
    win = (jnp.asarray(window, jnp.int32) if window is not None
           else jnp.int32(1 << 30))
    lm = (kv_len_mask if kv_len_mask is not None
          else jnp.ones((B, Sk), bool))

    def local(qb, kb, vb, lmb, off_, win_):
        # flat chunk index across seq_axes (major-to-minor, P-tuple order)
        idx = jnp.int32(0)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        kpos = idx * s_loc + jnp.arange(s_loc)[None, :]         # (1, s_loc)
        if per_row:
            # per-row cursors: row b's query sits at off_b + i; broadcast
            # to (B_loc, Sq, s_loc) so the mask resolves per row
            qpos = (off_[:, None, None]
                    + jnp.arange(qb.shape[1])[None, :, None])
        else:
            qpos = jnp.arange(qb.shape[1])[:, None] + off_      # (Sq, 1)
        qg = qb.reshape(qb.shape[0], qb.shape[1], KV, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kb).astype(jnp.float32)
        s = s * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = (kpos <= qpos) & (kpos > qpos - win_)
        if per_row:
            mask = mask[:, None, None]                          # (B,1,1,q,s)
        else:
            mask = mask[None, None, None]                       # (1,1,1,q,s)
        mask = mask & lmb[:, None, None, None, :]
        s = jnp.where(mask, s, _NEG)
        m_l = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m_l, seq_axes)
        p = jnp.where(mask, jnp.exp(s - m_g[..., None]), 0.0)
        l = jax.lax.psum(jnp.sum(p, axis=-1), seq_axes)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vb.dtype), vb)
        o = jax.lax.psum(pv.astype(jnp.float32), seq_axes)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.einsum("bkgqh->bqkgh", o).reshape(
            qb.shape[0], qb.shape[1], H, hd).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_entry, None, None, None),
                  P(b_entry, seq_axes, None, None),
                  P(b_entry, seq_axes, None, None),
                  P(b_entry, seq_axes),
                  P(b_entry) if per_row else P(), P()),
        out_specs=P(b_entry, None, None, None),
        check_vma=False,
    )(q, k, v, lm, off, win)


def _attn_core(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    *,
    causal_offset: jax.Array | int,   # q position i attends to j <= i + offset
    window: int | None,
    softcap: float | None,
    kv_len_mask: jax.Array | None = None,  # (B, Sk) valid-key mask (decode)
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    seq_axes: tuple[str, ...] | None = None,   # decode: S-sharded cache
) -> jax.Array:
    Sq, Sk = q.shape[1], k.shape[1]
    # split-K decode takes scalar AND per-row ((B,) vector) cursors: the
    # offset is sharded like q's batch axis and masked per K-shard, so the
    # continuous-batching path never regresses to plain attention under
    # tensor parallelism.  Flash q-chunking still assumes a shared qpos
    # base (prefill is per-request single-row, so its offset is scalar).
    per_row = jnp.ndim(causal_offset) == 1
    if seq_axes and Sq == 1 and Sk % max(
            1, _mesh_prod(jax.sharding.get_abstract_mesh(), seq_axes)) == 0:
        return _attn_decode_splitk(
            q, k, v, causal_offset=causal_offset, window=window,
            softcap=softcap, kv_len_mask=kv_len_mask, seq_axes=seq_axes)
    if (Sq > 1 and not per_row
            and Sq % q_chunk == 0 and Sk % kv_chunk == 0 and Sq >= q_chunk):
        return _attn_flash(
            q, k, v, causal_offset=causal_offset, window=window,
            softcap=softcap, kv_len_mask=kv_len_mask,
            q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
    return _attn_plain(
        q, k, v, causal_offset=causal_offset, window=window,
        softcap=softcap, kv_len_mask=kv_len_mask,
    )


def attention(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    window: int | None = None,
    cache: dict | None = None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
    causal: bool = True,
) -> tuple[jax.Array, dict | None]:
    """Self- or cross-attention with optional KV cache (decode).

    cache: {"k": (B, Smax, KV, hd), "v": ..., "pos": scalar int32} -- new keys
    are written at [pos : pos+Sq] and attention runs over the full cache with
    a validity mask.  ``pos`` may instead be a (B,) vector of per-row decode
    cursors (continuous batching): row b writes at [pos_b : pos_b+Sq] and
    masks keys >= pos_b+Sq, so a batch of requests at ragged positions
    decodes in one step.  Returns (out, updated_cache).
    """
    B, Sq, d = x.shape
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    q = constrain(q, "batch", None, "model", None)
    if cross_kv is None:
        k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
        v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
        if cfg.rope_mode != "none":
            cos, sin = rope_angles(cfg, positions)
            q = apply_rope(q, cos, sin, cfg)
            k = apply_rope(k, cos, sin, cfg)
    else:
        k, v = cross_kv
        if cfg.rope_mode != "none":
            cos, sin = rope_angles(cfg, positions)
            q = apply_rope(q, cos, sin, cfg)

    new_cache = None
    if cache is not None:
        pos = cache["pos"]
        if jnp.ndim(pos) == 1:
            # per-row cursors: row b writes its Sq new keys at pos_b
            def _row_upd(c, new, p):
                return jax.lax.dynamic_update_slice(c, new, (p, 0, 0))

            ck = jax.vmap(_row_upd)(cache["k"], k.astype(cache["k"].dtype), pos)
            cv = jax.vmap(_row_upd)(cache["v"], v.astype(cache["v"].dtype), pos)
            kv_len_mask = (jnp.arange(ck.shape[1])[None, :]
                           < (pos + Sq)[:, None])
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
            kv_len_mask = (jnp.arange(ck.shape[1]) < pos + Sq)[None].astype(bool)
            kv_len_mask = jnp.broadcast_to(kv_len_mask, (B, ck.shape[1]))
        new_cache = {"k": ck, "v": cv, "pos": pos + Sq}
        # which mesh axes shard the cache's sequence axis (split-K decode)
        tp = axis_size("model")
        bat_prod = axis_size("pod") * axis_size("data")
        if tp > 1 and B % max(bat_prod, 1) != 0:
            seq_axes = tuple(a for a in ("pod", "data", "model")
                             if axis_size(a) > 1)          # long-context B=1
        elif tp > 1 and cfg.n_kv_heads_eff % tp != 0:
            seq_axes = ("model",)                          # few-KV-head GQA
        else:
            seq_axes = None                                # KV-head sharded
        out = _attn_core(
            q, ck, cv,
            causal_offset=pos,
            window=window,
            softcap=cfg.attn_softcap,
            kv_len_mask=kv_len_mask,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            seq_axes=seq_axes,
        )
    else:
        # cross-attn / bidirectional: every query sees every key
        offset = 0 if (cross_kv is None and causal) else k.shape[1]
        out = _attn_core(
            q, k, v,
            causal_offset=offset,
            window=window,
            softcap=cfg.attn_softcap,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        )
    out = constrain(out, "batch", None, "model", None)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"]), new_cache


# ----------------------------------- MLP -----------------------------------

def mlp_init(key, cfg: ModelConfig, d: int | None = None, ff: int | None = None) -> Params:
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], (d, ff), dt),
            "w_up": _dense_init(ks[1], (d, ff), dt),
            "w_down": _dense_init(ks[2], (ff, d), dt, ff),
        }
    return {
        "w_up": _dense_init(ks[0], (d, ff), dt),
        "w_down": _dense_init(ks[1], (ff, d), dt, ff),
    }


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        act = jax.nn.silu(g) if cfg.mlp == "swiglu" else jax.nn.gelu(g)
        h = act * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = jax.nn.gelu(h) if cfg.mlp == "gelu" else jax.nn.relu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# -------------------------------- embedding --------------------------------

def embed_init(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    # vocab rows padded up to cfg.vocab_eff (zero rows) so the vocab axis is
    # TP-shardable; logits for padded ids are masked at the loss.
    table = _pad_heads(
        _dense_init(ks[0], (cfg.vocab, cfg.d_model), dt, cfg.d_model), 0,
        cfg.vocab_eff)
    if cfg.tie_embeddings:
        # tied: vocab-parallel (rows over "model"); looked up via the
        # explicit masked-gather shard_map below (XLA's auto-SPMD falls
        # back to full-table all-gathers for gathers over sharded rows).
        return {"table_tied": table}
    return {
        "table": table,   # untied: d over "model", rows replicated
        "unembed": _pad_heads(
            _dense_init(ks[1], (cfg.d_model, cfg.vocab), dt), 1, cfg.vocab_eff),
    }


def _vocab_parallel_gather(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Masked local gather + psum over the "model"-sharded vocab axis."""
    from jax.sharding import PartitionSpec as P

    tp = axis_size("model")
    V = table.shape[0]
    if tp <= 1 or V % tp != 0:
        return jnp.take(table, tokens, axis=0)
    mesh = jax.sharding.get_abstract_mesh()
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    vs = V // tp
    b_entry = batch_axes if batch_axes and tokens.shape[0] % _mesh_prod(
        mesh, batch_axes) == 0 else None

    def local(tok, tbl):
        lo = jax.lax.axis_index("model") * vs
        rel = jnp.clip(tok - lo, 0, vs - 1)
        out = jnp.take(tbl, rel, axis=0)
        mask = ((tok >= lo) & (tok < lo + vs))[..., None]
        out = jnp.where(mask, out, jnp.zeros((), out.dtype))
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_entry, None), P("model", None)),
        out_specs=P(b_entry, None, None),
        check_vma=False,
    )(tokens, table)


def _mesh_prod(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "table_tied" in p:
        return _vocab_parallel_gather(p["table_tied"], tokens)
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = p["table_tied"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
    logits = constrain(logits, "batch", None, "model")
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits
