"""Attention variants for the assigned architectures.

* **GQA** (grouped-query attention) — granite/command-r/codeqwen/qwen2.5/
  internvl2/olmoe/musicgen (kv == H is plain MHA, a special case).
* **MLA** (multi-head latent attention) — deepseek-v2: KV compressed to a
  ``kv_lora_rank`` latent + a decoupled shared RoPE key; decode runs in the
  *absorbed* form (queries projected into the latent space) so the cache is
  (S, r + d_rope) per token instead of (S, 2·H·dh).
* **Sliding-window** masking — zamba2's shared attention block at 500k
  context.

Train/prefill use a streaming-softmax (flash-style) formulation: an
``lax.scan`` over KV chunks with running (max, denom, acc) carried in fp32,
so the (S × S) score matrix is never materialized — the memory-roofline
requirement for the 32k-prefill shape cells.  Numerics are validated against
the naive materialized reference in tests.

Decode paths take the full KV cache and one new token per sequence.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import Initializer, apply_rope, he_init

__all__ = [
    "init_gqa", "gqa_prefill", "gqa_decode",
    "init_mla", "mla_prefill", "mla_decode_inputs", "mla_decode_attend",
    "flash_attention", "plain_attention",
]

_NEG = -1e30


# ----------------------------------------------------------- core attention
def plain_attention(
    q: jax.Array,            # (B, Sq, H, dh)
    k: jax.Array,            # (B, Sk, KV, dh)
    v: jax.Array,            # (B, Sk, KV, dhv)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
) -> jax.Array:
    """Naive materialized attention — the oracle for ``flash_attention``."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, KV, G, dh).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def flash_attention(
    q: jax.Array,            # (B, Sq, H, dh)
    k: jax.Array,            # (B, Sk, KV, dh)
    v: jax.Array,            # (B, Sk, KV, dhv)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_chunk: int = 1024,
    scale: float | None = None,
    probs_bf16: bool = False,
) -> jax.Array:
    """Streaming-softmax attention: scan over KV chunks, fp32 running stats.

    Peak live memory per step is O(Sq · kv_chunk) instead of O(Sq · Sk).
    ``probs_bf16`` casts the (Sq × chunk) probability matrix to bf16 for the
    P·V product — softmax stats (max/denominator) stay fp32, so the error is
    one rounding of p ∈ [0, 1] (≈1e-3 relative); halves the dominant score-
    matrix HBM traffic (EXPERIMENTS.md §Perf).
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dhv = v.shape[-1]
    G = H // KV
    scale = dh ** -0.5 if scale is None else scale
    kv_chunk = min(kv_chunk, Sk)
    if Sk % kv_chunk:
        pad = (-Sk) % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sk_p = Sk + pad
    else:
        Sk_p = Sk
    n_chunks = Sk_p // kv_chunk

    qg = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, dh)
    qpos = jnp.arange(Sq) + q_offset
    # scan inputs: chunked keys/values (n, B, ck, KV, d)
    kc = jnp.moveaxis(k.reshape(B, n_chunks, kv_chunk, KV, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, kv_chunk, KV, dhv), 1, 0)

    def step(carry, inp):
        m, l, acc = carry                       # (B,KV,G,Sq), same, (B,KV,G,Sq,dhv)
        ci, kci, vci = inp
        kpos = ci * kv_chunk + jnp.arange(kv_chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kci.astype(jnp.float32))
        mask = kpos[None, :] < Sk               # padded keys
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        if probs_bf16:
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(jnp.bfloat16),
                            vci.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vci.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, dhv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, dhv).astype(q.dtype)


# ------------------------------------------------------------------------ GQA
def init_gqa(
    ini: Initializer,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_head: int,
    *,
    bias: bool = False,
    dtype=jnp.float32,
) -> dict[str, Any]:
    p = {
        "wq": he_init(ini, (d_model, n_heads, d_head), d_model, dtype),
        "wk": he_init(ini, (d_model, n_kv_heads, d_head), d_model, dtype),
        "wv": he_init(ini, (d_model, n_kv_heads, d_head), d_model, dtype),
        "wo": he_init(ini, (n_heads, d_head, d_model), n_heads * d_head, dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads, d_head), dtype)
        p["bk"] = jnp.zeros((n_kv_heads, d_head), dtype)
        p["bv"] = jnp.zeros((n_kv_heads, d_head), dtype)
    return p


def _qkv(p: dict[str, Any], x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def gqa_prefill(
    p: dict[str, Any],
    x: jax.Array,            # (B, S, D)
    cos: jax.Array,
    sin: jax.Array,
    *,
    window: int = 0,
    kv_chunk: int = 1024,
    probs_bf16: bool = False,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence causal attention; returns (out, (k, v)) for the cache."""
    q, k, v = _qkv(p, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    out = flash_attention(q, k, v, causal=True, window=window, kv_chunk=kv_chunk,
                          probs_bf16=probs_bf16)
    dt = x.dtype
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    return y, (k, v)


def gqa_decode(
    p: dict[str, Any],
    x: jax.Array,            # (B, 1, D) — one new token
    k_cache: jax.Array,      # (B, S_max, KV, dh)
    v_cache: jax.Array,
    pos: jax.Array,          # (B,) int32 — current length (new token's index)
    cos: jax.Array,          # (1, dh/2) rope row for this position
    sin: jax.Array,
    *,
    window: int = 0,
    write_pos: jax.Array | None = None,   # ring-buffer slot (defaults to pos)
    valid_len: jax.Array | None = None,   # #valid cache slots (ring caches)
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Single-token decode against the cache; returns (out, updated caches).

    For a full-length cache, pass only ``pos``.  For a ring-buffer (sliding
    window) cache of width W, pass ``write_pos = pos % W`` and
    ``valid_len = min(pos + 1, W)``; RoPE is applied at the *absolute*
    position before caching, so slot order does not matter.
    """
    B, _, D = x.shape
    S = k_cache.shape[1]
    wp = pos if write_pos is None else write_pos
    q, k, v = _qkv(p, x)                       # (B, 1, H/KV, dh)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # write the new K/V at each sequence's slot
    onehot = (jnp.arange(S)[None, :] == wp[:, None]).astype(k_cache.dtype)
    k_cache = k_cache * (1 - onehot)[..., None, None] + k * onehot[..., None, None]
    v_cache = v_cache * (1 - onehot)[..., None, None] + v * onehot[..., None, None]

    H, dh = q.shape[2], q.shape[3]
    KV = k_cache.shape[2]
    G = H // KV
    qg = q[:, 0].reshape(B, KV, G, dh).astype(jnp.float32) * (dh ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    kpos = jnp.arange(S)[None, :]
    if valid_len is not None:
        mask = kpos < valid_len[:, None]
    else:
        mask = kpos <= pos[:, None]
        if window:
            mask &= kpos > (pos[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgs,bskd->bkgd", pr, v_cache.astype(jnp.float32))
    ctx = ctx.reshape(B, 1, H, dh).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y, (k_cache, v_cache)


# ------------------------------------------------------------------------ MLA
def init_mla(
    ini: Initializer,
    d_model: int,
    n_heads: int,
    *,
    kv_lora_rank: int,
    q_lora_rank: int,
    d_head: int,             # nope dims per head (== value dims here)
    d_rope: int,
    dtype=jnp.float32,
) -> dict[str, Any]:
    H, r, rq, dn, dr = n_heads, kv_lora_rank, q_lora_rank, d_head, d_rope
    p: dict[str, Any] = {
        "w_dkv": he_init(ini, (d_model, r), d_model, dtype),
        "norm_kv": jnp.ones((r,), dtype),
        "w_kr": he_init(ini, (d_model, dr), d_model, dtype),
        "w_uk": he_init(ini, (r, H, dn), r, dtype),
        "w_uv": he_init(ini, (r, H, dn), r, dtype),
        "wo": he_init(ini, (H, dn, d_model), H * dn, dtype),
    }
    if rq:
        p["w_dq"] = he_init(ini, (d_model, rq), d_model, dtype)
        p["norm_q"] = jnp.ones((rq,), dtype)
        p["w_uq"] = he_init(ini, (rq, H, dn), rq, dtype)
        p["w_qr"] = he_init(ini, (rq, H, dr), rq, dtype)
    else:
        p["w_uq"] = he_init(ini, (d_model, H, dn), d_model, dtype)
        p["w_qr"] = he_init(ini, (d_model, H, dr), d_model, dtype)
    return p


def _mla_q(p: dict[str, Any], x: jax.Array,
           eps: float = 1e-5) -> tuple[jax.Array, jax.Array]:
    from repro.models.layers import rms_norm

    dt = x.dtype
    if "w_dq" in p:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
        cq = rms_norm(cq, p["norm_q"], eps)
    else:
        cq = x
    q_nope = jnp.einsum("bsr,rhd->bshd", cq, p["w_uq"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
    q_rope = jnp.einsum("bsr,rhd->bshd", cq, p["w_qr"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
    return q_nope, q_rope


def _mla_latent(p: dict[str, Any], x: jax.Array,
                eps: float = 1e-5) -> tuple[jax.Array, jax.Array]:
    from repro.models.layers import rms_norm

    dt = x.dtype
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)
    c_kv = rms_norm(c_kv, p["norm_kv"], eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_kr"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
    return c_kv, k_rope


def mla_prefill(
    p: dict[str, Any],
    x: jax.Array,            # (B, S, D)
    cos: jax.Array,
    sin: jax.Array,
    *,
    kv_chunk: int = 1024,
    probs_bf16: bool = False,
    scale: float | None = None,
    eps: float = 1e-5,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Materialized-KV MLA for train/prefill; caches (c_kv, k_rope) only.
    ``scale`` is the softmax scale, ``(dn + dr)^-½`` where None; ``eps``
    the latent norms' epsilon."""
    dt = x.dtype
    B, S, D = x.shape
    q_nope, q_rope = _mla_q(p, x, eps)
    dn = q_nope.shape[-1]
    dr = q_rope.shape[-1]
    c_kv, k_rope = _mla_latent(p, x, eps)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    # materialize per-head keys/values from the latent (train/prefill path)
    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_uk"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsr,rhd->bshd", c_kv, p["w_uv"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    H = k_nope.shape[2]
    # append the shared rope key to every head; query gets its own rope part
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], axis=-1
    )
    scale = (dn + dr) ** -0.5 if scale is None else scale
    out = flash_attention(q, k, v, causal=True, kv_chunk=kv_chunk, scale=scale,
                          probs_bf16=probs_bf16)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    return y, (c_kv, k_rope)


def mla_decode_inputs(
    p: dict[str, Any],
    x: jax.Array,             # (B, 1, D)
    cos: jax.Array,
    sin: jax.Array,
    eps: float = 1e-5,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The new token's part of absorbed-form decode: the latent-space query
    ``q_nope · W_uk`` (B, H, r) in float32, the roped query (B, H, dr), and
    the cache rows it adds, ``c_kv`` (B, r) and roped ``k_rope`` (B, dr)."""
    dt = x.dtype
    q_nope, q_rope = _mla_q(p, x, eps)                  # (B,1,H,dn/dr)
    q_rope = apply_rope(q_rope, cos, sin)
    c_kv, k_rope = _mla_latent(p, x, eps)               # (B,1,r), (B,1,dr)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], p["w_uk"].astype(dt),
                       preferred_element_type=jnp.float32)
    return q_lat, q_rope[:, 0], c_kv[:, 0], k_rope[:, 0]


def mla_decode_attend(
    p: dict[str, Any],
    q_lat: jax.Array,         # (B, H, r) float32
    q_rope: jax.Array,        # (B, H, dr)
    ckv_cache: jax.Array,     # (B, S_max, r), the new rows written
    krope_cache: jax.Array,   # (B, S_max, dr)
    pos: jax.Array,           # (B,)
    scale: float,
    dt,
) -> jax.Array:
    """Attention in the latent space over cache rows ``0..pos``:
    scores = q_lat · c_kv + q_rope · k_rope; out = softmax · c_kv · W_uv · W_o.
    Operands in ``dt``, sums and the softmax in float32.  Returns (B, 1, D)."""
    S = ckv_cache.shape[1]
    f32 = jnp.float32
    s = jnp.einsum("bhr,bsr->bhs", q_lat.astype(dt), ckv_cache.astype(dt),
                   preferred_element_type=f32)
    s = s + jnp.einsum("bhd,bsd->bhs", q_rope.astype(dt),
                       krope_cache.astype(dt), preferred_element_type=f32)
    s = s * scale
    mask = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG)
    pr = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhs,bsr->bhr", pr.astype(dt), ckv_cache.astype(dt),
                         preferred_element_type=f32)
    ctx = jnp.einsum("bhr,rhd->bhd", ctx_lat.astype(dt), p["w_uv"].astype(dt),
                     preferred_element_type=f32)
    y = jnp.einsum("bhk,hkd->bd", ctx.astype(dt), p["wo"].astype(dt),
                   preferred_element_type=f32)
    return y[:, None, :].astype(dt)
