"""Unified LM model covering all assigned architecture families.

One parameter tree + three entry points:

* ``forward_full``   — teacher-forced full-sequence forward (train & prefill;
  prefill additionally returns the serving caches),
* ``forward_decode`` — one new token per sequence against carried caches
  (KV cache / MLA latent cache / SSM state, per family),
* ``init_cache``     — abstract or concrete cache allocation.

Families (``ModelConfig.family``):
  dense   — pre-norm GQA transformer (granite, command-r, codeqwen, qwen2.5,
            musicgen backbone, internvl2 backbone)
  moe     — GQA or MLA attention + top-k routed experts (olmoe, deepseek-v2);
            ``n_dense_layers`` leading layers keep a dense SwiGLU of
            ``d_ff`` (deepseek-v2-lite's ``first_k_dense_replace``)
  ssm     — attention-free Mamba2 SSD stack (mamba2-1.3b)
  hybrid  — Mamba2 backbone with a *shared* attention block applied every
            ``hybrid_attn_every`` layers (zamba2-7b); the shared block runs at
            2×d_model on concat(hidden, initial embedding), Zamba-style.

Layers are stacked (leading L axis) and driven by ``lax.scan`` so the lowered
HLO stays compact for the 512-device dry-run; each block is wrapped in
``jax.checkpoint`` (nothing saveable) when ``cfg.remat``.  A moe model with
leading dense layers has two stacks, ``dense_blocks`` then ``blocks``, each
its own scan; its cache leaves keep one layer axis over both.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models.layers import (
    Initializer,
    YaRN,
    cross_entropy_loss,
    he_init,
    init_mlp,
    mlp_swiglu,
    pad_vocab,
    rms_norm,
    rope_freqs,
    rope_table,
)
from repro.sharding.ctx import shard_act

__all__ = ["ModelConfig", "init_params", "abstract_params", "forward_full",
           "forward_decode", "init_cache", "lm_loss", "count_params"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid
    n_layers: int
    d_model: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    # --- MoE
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    n_dense_layers: int = 0         # leading layers with a dense FFN (d_ff)
    norm_topk_prob: bool = True     # renormalise the chosen gates to sum 1
    routed_scaling_factor: float = 1.0
    moe_impl: str = "capacity"      # capacity (training) | dropless (serving)
    # --- MLA (deepseek-v2)
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    d_rope: int = 0
    # --- SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # --- hybrid (zamba2)
    hybrid_attn_every: int = 0
    attn_window: int = 0            # sliding window; 0 = full causal
    # --- misc
    qkv_bias: bool = False
    # pad MHA head counts up to a multiple (TP feasibility: e.g. musicgen's
    # 24 heads → 32 so they shard over a 16-way model axis).  The padded
    # output-projection rows are zero-initialized, so the function is
    # unchanged at init.  Only valid for MHA (n_kv_heads == n_heads): padding
    # GQA would change the query→KV group mapping.
    head_pad_multiple: int = 0
    # bf16 attention probabilities for the P·V product (fp32 softmax stats
    # kept) — halves the flash score traffic; see attention.flash_attention.
    attn_probs_bf16: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e4
    rope_scaling: YaRN | None = None
    modality: str = "text"          # text | audio_tokens | vision_prefix
    vision_prefix_len: int = 0
    act_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    vocab_pad_multiple: int = 256
    kv_chunk: int = 1024
    remat: bool = True
    remat_policy: str = "nothing"     # nothing | dots (save matmul outputs)

    # ------------------------------------------------------------ derived
    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_pad_multiple)

    @property
    def adt(self):
        return jnp.dtype(self.act_dtype)

    @property
    def pdt(self):
        return jnp.dtype(self.param_dtype)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.family == "moe" else 0

    @property
    def rope_dim(self) -> int:
        return self.d_rope if self.use_mla else self.d_head

    @property
    def mla_scale(self) -> float:
        """MLA's softmax scale: (qk_nope + qk_rope)^-½, times YaRN's
        mscale(factor, mscale_all_dim)²."""
        f = 1.0 if self.rope_scaling is None else self.rope_scaling.softmax_factor
        return (self.d_head + self.d_rope) ** -0.5 * f

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def d_conv_ch(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    # hybrid layout: n_groups × (every-1 mamba + 1 shared attn) + tail mamba
    @property
    def hybrid_groups(self) -> int:
        return self.n_layers // self.hybrid_attn_every if self.hybrid_attn_every else 0

    @property
    def hybrid_tail(self) -> int:
        return self.n_layers - self.hybrid_groups * self.hybrid_attn_every

    @property
    def n_mamba_layers(self) -> int:
        if self.family == "ssm":
            return self.n_layers
        if self.family == "hybrid":
            return self.hybrid_groups * (self.hybrid_attn_every - 1) + self.hybrid_tail
        return 0

    @property
    def uses_attention(self) -> bool:
        return self.family in ("dense", "moe", "hybrid")

    @property
    def n_heads_eff(self) -> int:
        if self.head_pad_multiple and not self.use_mla:
            assert self.n_kv_heads == self.n_heads, (
                "head padding is only function-preserving for MHA")
            m = self.head_pad_multiple
            return -(-self.n_heads // m) * m
        return self.n_heads

    @property
    def n_kv_heads_eff(self) -> int:
        if self.head_pad_multiple and not self.use_mla:
            return self.n_heads_eff if self.n_kv_heads == self.n_heads else self.n_kv_heads
        return self.n_kv_heads


# =============================================================== param init
def _stack(fn, n: int):
    """Initialize ``n`` stacked layer subtrees via vmap over fold_in keys."""

    def init_one(key):
        return fn(Initializer(key))

    def stacked(ini: Initializer):
        keys = jax.random.split(ini.next_key(), n)
        return jax.vmap(init_one)(keys)

    return stacked


def _init_attn_block(cfg: ModelConfig, ini: Initializer,
                     moe: bool = False) -> dict[str, Any]:
    dt = cfg.pdt
    blk: dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), dt),
                           "norm2": jnp.ones((cfg.d_model,), dt)}
    if cfg.use_mla:
        blk["attn"] = attn.init_mla(
            ini, cfg.d_model, cfg.n_heads,
            kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
            d_head=cfg.d_head, d_rope=cfg.d_rope, dtype=dt,
        )
    else:
        blk["attn"] = attn.init_gqa(
            ini, cfg.d_model, cfg.n_heads_eff, cfg.n_kv_heads_eff, cfg.d_head,
            bias=cfg.qkv_bias, dtype=dt,
        )
        if cfg.n_heads_eff != cfg.n_heads:
            # zero the padded heads' output rows → identical function at init
            wo = blk["attn"]["wo"]
            blk["attn"]["wo"] = wo.at[cfg.n_heads:].set(0.0)
    if moe:
        blk["moe"] = moe_mod.init_moe(
            ini, cfg.d_model, cfg.d_ff_expert, cfg.n_experts,
            n_shared=cfg.n_shared_experts, dtype=dt,
        )
    else:
        blk["mlp"] = init_mlp(ini, cfg.d_model, cfg.d_ff, dt)
    return blk


def _init_mamba_block(cfg: ModelConfig, ini: Initializer) -> dict[str, Any]:
    dt = cfg.pdt
    return {
        "norm1": jnp.ones((cfg.d_model,), dt),
        "ssm": m2.init_mamba2(
            ini, cfg.d_model, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
            expand=cfg.ssm_expand, conv_width=cfg.ssm_conv, dtype=dt,
        ),
    }


def _init_shared_attn(cfg: ModelConfig, ini: Initializer) -> dict[str, Any]:
    """Zamba2-style shared block at 2×d_model over concat(h, emb0)."""
    dt = cfg.pdt
    d2 = 2 * cfg.d_model
    return {
        "norm1": jnp.ones((d2,), dt),
        "norm2": jnp.ones((d2,), dt),
        "attn": attn.init_gqa(ini, d2, cfg.n_heads, cfg.n_kv_heads,
                              d2 // cfg.n_heads, dtype=dt),
        "mlp": init_mlp(ini, d2, cfg.d_ff, dt),
        "out": he_init(ini, (d2, cfg.d_model), d2, dt),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> dict[str, Any]:
    ini = Initializer(key)
    dt = cfg.pdt
    Vp, D = cfg.padded_vocab, cfg.d_model
    params: dict[str, Any] = {
        "embed": ini.normal((Vp, D), 0.02, dt),
        "final_norm": jnp.ones((D,), dt),
        "lm_head": he_init(ini, (D, Vp), D, dt),
    }
    if cfg.family == "dense":
        params["blocks"] = _stack(
            functools.partial(_init_attn_block, cfg), cfg.n_layers
        )(ini)
    elif cfg.family == "moe":
        if cfg.n_dense_layers:
            params["dense_blocks"] = _stack(
                functools.partial(_init_attn_block, cfg), cfg.n_dense_layers
            )(ini)
        params["blocks"] = _stack(
            functools.partial(_init_attn_block, cfg, moe=True),
            cfg.n_moe_layers)(ini)
    elif cfg.family == "ssm":
        params["blocks"] = _stack(
            functools.partial(_init_mamba_block, cfg), cfg.n_layers
        )(ini)
    elif cfg.family == "hybrid":
        params["blocks"] = _stack(
            functools.partial(_init_mamba_block, cfg), cfg.n_mamba_layers
        )(ini)
        params["shared_attn"] = _init_shared_attn(cfg, ini)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    return params


def abstract_params(cfg: ModelConfig) -> Any:
    """ShapeDtypeStruct tree (no allocation) — what the dry-run lowers with."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def count_params(cfg: ModelConfig) -> int:
    import math

    tree = abstract_params(cfg)
    return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))


# ================================================================== forward
_REMAT_POLICIES = {
    "nothing": lambda: jax.checkpoint_policies.nothing_saveable,
    # saves every dot incl. attention scores — blows VMEM/HBM working set
    # at 32k-class shapes (measured 30 GB temp on command-r); kept for
    # ablation only
    "dots": lambda: jax.checkpoint_policies.dots_saveable,
    # saves weight matmul outputs (no batch dims) but recomputes attention
    # scores — the compute/memory sweet spot (EXPERIMENTS.md §Perf)
    "dots_nobatch": lambda: jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat:
        return jax.checkpoint(fn, policy=_REMAT_POLICIES[cfg.remat_policy]())
    return fn


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _scanned_experts(cfg: ModelConfig, blocks):
    """A layer stack as its scan takes it: (the blocks it slices, the routed
    expert weights it closes over whole or None).  On the dropless path the
    grouped matmul is a custom call, which takes no fused slice: a sliced
    layer of experts would be copied out of the stack in every layer of
    every call, so they stay whole and are read in place by layer index."""
    if cfg.moe_impl != "dropless" or "moe" not in blocks:
        return blocks, None
    moe = dict(blocks["moe"])
    experts = {n: moe.pop(n) for n in _EXPERT_WEIGHTS}
    return {**blocks, "moe": moe}, experts


def _with_experts(blk, experts):
    """A scanned block with the whole expert stacks put back."""
    if experts is None:
        return blk
    return {**blk, "moe": {**blk["moe"], **experts}}


def _ffn(cfg: ModelConfig, blk, h, count_mask, layer=0):
    """The block's FFN: (output, aux loss, routed choices per expert or
    None).  Dense blocks have no experts; ``layer`` is the block's index in
    its stack, which the dropless path's whole expert stacks are read at."""
    if "moe" not in blk:
        return mlp_swiglu(blk["mlp"], h), jnp.zeros((), jnp.float32), None
    k = cfg.experts_per_token
    if cfg.moe_impl == "dropless":
        f, counts = moe_mod.moe_dropless(
            blk["moe"], h, k=k, layer=layer,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            count_mask=count_mask)
        return f, jnp.zeros((), jnp.float32), counts
    f, aux = moe_mod.moe_ffn(blk["moe"], h, k=k,
                             capacity_factor=cfg.capacity_factor,
                             norm_topk_prob=cfg.norm_topk_prob,
                             routed_scaling_factor=cfg.routed_scaling_factor)
    counts = (None if count_mask is None else
              moe_mod.expert_counts(blk["moe"], h, k=k, mask=count_mask))
    return f, aux, counts


def _attn_block_full(cfg: ModelConfig, blk, x, cos, sin, window,
                     count_mask=None, layer=0):
    h = rms_norm(x, blk["norm1"], cfg.norm_eps)
    if cfg.use_mla:
        a, cache = attn.mla_prefill(blk["attn"], h, cos, sin, kv_chunk=cfg.kv_chunk,
                                    probs_bf16=cfg.attn_probs_bf16,
                                    scale=cfg.mla_scale, eps=cfg.norm_eps)
    else:
        a, cache = attn.gqa_prefill(blk["attn"], h, cos, sin, window=window,
                                    kv_chunk=cfg.kv_chunk,
                                    probs_bf16=cfg.attn_probs_bf16)
    x = x + a
    h = rms_norm(x, blk["norm2"], cfg.norm_eps)
    f, aux, counts = _ffn(cfg, blk, h, count_mask, layer)
    return x + f, cache, aux, counts


def _shared_block_full(cfg: ModelConfig, sp, x, emb0, cos2, sin2, window):
    z = jnp.concatenate([x, emb0], axis=-1)
    h = rms_norm(z, sp["norm1"], cfg.norm_eps)
    a, cache = attn.gqa_prefill(sp["attn"], h, cos2, sin2, window=window,
                                kv_chunk=cfg.kv_chunk)
    z = z + a
    h = rms_norm(z, sp["norm2"], cfg.norm_eps)
    z = z + mlp_swiglu(sp["mlp"], h)
    y = jnp.einsum("bse,ed->bsd", z, sp["out"].astype(z.dtype),
                   preferred_element_type=jnp.float32).astype(z.dtype)
    return x + y, cache


def _embed(cfg: ModelConfig, params, tokens, prefix_embeds):
    emb = jnp.take(params["embed"].astype(cfg.adt), tokens, axis=0)
    if prefix_embeds is not None:
        emb = jnp.concatenate([prefix_embeds.astype(cfg.adt), emb], axis=1)
    return emb


def _stacks(params) -> list:
    """The attention families' layer stacks in order: the leading dense
    layers (moe models that have them), then the rest."""
    return [params[k] for k in ("dense_blocks", "blocks") if k in params]


def _stack_len(blocks) -> int:
    return jax.tree.leaves(blocks)[0].shape[0]


def _cat_layers(parts: list):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def forward_full(
    params: dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,                      # (B, S_text) int32
    *,
    prefix_embeds: jax.Array | None = None,  # (B, Np, D) — vision stub
    window: int | None = None,
    return_cache: bool = False,
    head_positions: jax.Array | None = None,  # (B,) int32
    count_mask: jax.Array | None = None,      # (B, S) bool
) -> tuple:
    """Teacher-forced forward.  Returns (logits (B,S,Vp), caches|None, aux).

    ``head_positions`` computes the norm and the LM head at one position
    per row only, giving logits (B, Vp).  Where ``count_mask`` is given a
    fourth element follows: the routed choices per expert of the tokens it
    keeps, (MoE layers, E) int32 (None for a model with no experts)."""
    window = cfg.attn_window if window is None else window
    x = shard_act(_embed(cfg, params, tokens, prefix_embeds), "hidden")
    B, S, D = x.shape
    aux_total = jnp.zeros((), jnp.float32)
    caches = counts = None

    if cfg.family in ("dense", "moe"):
        cos, sin = rope_table(S, cfg.rope_dim, cfg.rope_theta,
                              scaling=cfg.rope_scaling)

        def body(experts, carry, blk):
            h, aux, j = carry
            h2, cache, a, c = _maybe_remat(
                lambda b, hh, jj: _attn_block_full(cfg, b, hh, cos, sin,
                                                   window, count_mask, jj),
                cfg
            )(_with_experts(blk, experts), h, j)
            out = cache if return_cache else None
            return (h2, aux + a, j + 1), (out, c)

        cache_parts, count_parts = [], []
        for blocks in _stacks(params):
            blocks, experts = _scanned_experts(cfg, blocks)
            (x, aux_total, _), (cache, c) = jax.lax.scan(
                functools.partial(body, experts),
                (x, aux_total, jnp.int32(0)), blocks)
            cache_parts.append(cache)
            if c is not None:
                count_parts.append(c)
        if count_parts:
            counts = _cat_layers(count_parts)
        if return_cache:
            c0 = _cat_layers([c[0] for c in cache_parts])
            c1 = _cat_layers([c[1] for c in cache_parts])
            caches = ({"ckv": c0, "kr": c1} if cfg.use_mla
                      else {"k": c0, "v": c1})

    elif cfg.family == "ssm":
        def body(h, blk):
            def blk_fn(b, hh):
                y, st = m2.mamba2_prefill(b["ssm"], rms_norm(hh, b["norm1"], cfg.norm_eps),
                                          chunk=cfg.ssm_chunk)
                return hh + y, st
            h2, st = _maybe_remat(blk_fn, cfg)(blk, h)
            return h2, st if return_cache else None

        x, states = jax.lax.scan(body, x, params["blocks"])
        if return_cache:
            caches = dict(zip(("h", "conv_x", "conv_b", "conv_c"), states))

    elif cfg.family == "hybrid":
        x, caches = _hybrid_full(params, cfg, x, window, return_cache)
    else:
        raise ValueError(cfg.family)

    x = shard_act(x, "hidden")
    if head_positions is not None:
        x = x[jnp.arange(B), head_positions][:, None, :]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.adt),
                        preferred_element_type=jnp.float32)
    logits = shard_act(logits, "logits")
    if head_positions is not None:
        logits = logits[:, 0]
    if count_mask is not None:
        return logits, caches, aux_total, counts
    return logits, caches, aux_total


def _hybrid_full(params, cfg: ModelConfig, x, window, return_cache):
    B, S, D = x.shape
    emb0 = x
    k = cfg.hybrid_attn_every
    G, tail = cfg.hybrid_groups, cfg.hybrid_tail
    blocks = params["blocks"]
    grouped = jax.tree.map(lambda a: a[: G * (k - 1)].reshape((G, k - 1) + a.shape[1:]),
                           blocks)
    tail_blocks = jax.tree.map(lambda a: a[G * (k - 1):], blocks)
    sp = params["shared_attn"]
    d2 = 2 * D
    cos2, sin2 = rope_table(S, d2 // cfg.n_heads, cfg.rope_theta)

    def mamba_step(h, blk):
        def blk_fn(b, hh):
            y, st = m2.mamba2_prefill(b["ssm"], rms_norm(hh, b["norm1"], cfg.norm_eps),
                                      chunk=cfg.ssm_chunk)
            return hh + y, st
        h2, st = _maybe_remat(blk_fn, cfg)(blk, h)
        return h2, st if return_cache else None

    def group_step(h, grp_blocks):
        h, sts = jax.lax.scan(mamba_step, h, grp_blocks)
        h, kv = _maybe_remat(
            lambda s, hh: _shared_block_full(cfg, s, hh, emb0, cos2, sin2, window),
            cfg,
        )(sp, h)
        return h, (sts, kv if return_cache else None)

    x, (m_states, kvs) = jax.lax.scan(group_step, x, grouped)
    x, t_states = jax.lax.scan(mamba_step, x, tail_blocks)
    caches = None
    if return_cache:
        def _merge(a, b):  # (G, k-1, ...) + (tail, ...) → (n_mamba, ...)
            return jnp.concatenate([a.reshape((-1,) + a.shape[2:]), b], axis=0)
        caches = {
            name: _merge(m_states[i], t_states[i])
            for i, name in enumerate(("h", "conv_x", "conv_b", "conv_c"))
        }
        caches["k"], caches["v"] = kvs[0], kvs[1]   # (G, B, S, KV, dh2)
    return x, caches


# =================================================================== decode
def _rope_at(pos: jax.Array, dim: int, theta: float,
             scaling: YaRN | None = None) -> tuple[jax.Array, jax.Array]:
    freqs = rope_freqs(dim, theta, scaling)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]  # (B,1,half)
    if scaling is not None:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    return cos, sin


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, abstract: bool = False):
    """Serving cache pytree (zeros, or ShapeDtypeStructs when ``abstract``)."""
    adt = cfg.adt

    def mk(shape, dtype):
        return (jax.ShapeDtypeStruct(shape, dtype) if abstract
                else jnp.zeros(shape, dtype))

    L, B, S = cfg.n_layers, batch, max_len
    if cfg.family in ("dense", "moe"):
        if cfg.use_mla:
            return {"ckv": mk((L, B, S, cfg.kv_lora_rank), adt),
                    "kr": mk((L, B, S, cfg.d_rope), adt)}
        return {"k": mk((L, B, S, cfg.n_kv_heads_eff, cfg.d_head), adt),
                "v": mk((L, B, S, cfg.n_kv_heads_eff, cfg.d_head), adt)}
    if cfg.family == "ssm":
        W1 = cfg.ssm_conv - 1
        return {"h": mk((L, B, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                        jnp.float32),
                "conv_x": mk((L, B, W1, cfg.d_inner), adt),
                "conv_b": mk((L, B, W1, cfg.ssm_state), adt),
                "conv_c": mk((L, B, W1, cfg.ssm_state), adt)}
    if cfg.family == "hybrid":
        M, G = cfg.n_mamba_layers, cfg.hybrid_groups
        d2 = 2 * cfg.d_model
        W1 = cfg.ssm_conv - 1
        win = min(S, cfg.attn_window) if cfg.attn_window else S
        return {
            "h": mk((M, B, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
            "conv_x": mk((M, B, W1, cfg.d_inner), adt),
            "conv_b": mk((M, B, W1, cfg.ssm_state), adt),
            "conv_c": mk((M, B, W1, cfg.ssm_state), adt),
            "k": mk((G, B, win, cfg.n_kv_heads, d2 // cfg.n_heads), adt),
            "v": mk((G, B, win, cfg.n_kv_heads, d2 // cfg.n_heads), adt),
        }
    raise ValueError(cfg.family)


def _mla_decode_stacks(params, cfg: ModelConfig, x, caches, pos, cos, sin,
                       mask):
    """MLA decode over the layer stacks with the latent cache carried
    whole through each scan: the new token's rows are scattered into layer
    ``i`` in place and attention reads that layer's rows, so a donated
    cache is updated where it lies and never copied."""
    b = jnp.arange(x.shape[0])
    ckv, kr = caches["ckv"], caches["kr"]

    def body(experts, first, carry, blk):
        h, ckv, kr, i = carry
        blk = _with_experts(blk, experts)
        hn = rms_norm(h, blk["norm1"], cfg.norm_eps)
        q_lat, q_rope, c_new, k_new = attn.mla_decode_inputs(
            blk["attn"], hn, cos, sin, cfg.norm_eps)
        ckv = ckv.at[i, b, pos].set(c_new.astype(ckv.dtype))
        kr = kr.at[i, b, pos].set(k_new.astype(kr.dtype))
        a = attn.mla_decode_attend(blk["attn"], q_lat, q_rope, ckv[i], kr[i],
                                   pos, cfg.mla_scale, h.dtype)
        h = h + a
        f, _, c = _ffn(cfg, blk, rms_norm(h, blk["norm2"], cfg.norm_eps),
                       mask, i - first)
        return (h + f, ckv, kr, i + 1), c

    counts, layer = [], jnp.int32(0)
    for blocks in _stacks(params):
        blocks, experts = _scanned_experts(cfg, blocks)
        (x, ckv, kr, layer), c = jax.lax.scan(
            functools.partial(body, experts, layer), (x, ckv, kr, layer),
            blocks)
        if c is not None:
            counts.append(c)
    return x, {"ckv": ckv, "kr": kr}, counts


def _gqa_decode_stacks(params, cfg: ModelConfig, x, caches, pos, cos, sin,
                       mask):
    def body(experts, carry, xs):
        h, j = carry
        blk, c0, c1 = xs
        blk = _with_experts(blk, experts)
        hn = rms_norm(h, blk["norm1"], cfg.norm_eps)
        a, (c0, c1) = attn.gqa_decode(blk["attn"], hn, c0, c1, pos, cos, sin,
                                      window=cfg.attn_window)
        h = h + a
        f, _, c = _ffn(cfg, blk, rms_norm(h, blk["norm2"], cfg.norm_eps),
                       mask, j)
        return (h + f, j + 1), (c0, c1, c)

    stacks = _stacks(params)
    counts, new_k, new_v, layer = [], [], [], 0
    for blocks in stacks:
        n = _stack_len(blocks)
        k, v = caches["k"], caches["v"]
        if len(stacks) > 1:
            k, v = k[layer:layer + n], v[layer:layer + n]
        blocks, experts = _scanned_experts(cfg, blocks)
        (x, _), (k, v, c) = jax.lax.scan(functools.partial(body, experts),
                                         (x, jnp.int32(0)), (blocks, k, v))
        new_k.append(k)
        new_v.append(v)
        if c is not None:
            counts.append(c)
        layer += n
    return x, {"k": _cat_layers(new_k), "v": _cat_layers(new_v)}, counts


def forward_decode(
    params: dict[str, Any],
    cfg: ModelConfig,
    token: jax.Array,        # (B,) int32 — the newest token
    caches: Any,
    pos: jax.Array,          # (B,) int32 — its position (current length)
    *,
    count_mask: jax.Array | None = None,   # (B,) bool
) -> tuple:
    """One decode step; returns (logits (B, Vp), updated caches), and where
    ``count_mask`` is given a third element: the routed choices per expert
    of the rows it keeps, (MoE layers, E) int32, or None."""
    x = jnp.take(params["embed"].astype(cfg.adt), token[:, None], axis=0)
    counts = None

    if cfg.family in ("dense", "moe"):
        cos, sin = _rope_at(pos, cfg.rope_dim, cfg.rope_theta,
                            cfg.rope_scaling)
        mask = None if count_mask is None else count_mask[:, None]
        stacks = _mla_decode_stacks if cfg.use_mla else _gqa_decode_stacks
        x, caches, parts = stacks(params, cfg, x, caches, pos, cos, sin, mask)
        if parts:
            counts = _cat_layers(parts)

    elif cfg.family == "ssm":
        def body(h, xs):
            blk, st = xs
            y, st = m2.mamba2_decode(blk["ssm"],
                                     rms_norm(h, blk["norm1"], cfg.norm_eps), st)
            return h + y, st

        ckeys = ("h", "conv_x", "conv_b", "conv_c")
        x, new = jax.lax.scan(
            body, x, (params["blocks"], tuple(caches[k] for k in ckeys))
        )
        caches = dict(zip(ckeys, new))

    elif cfg.family == "hybrid":
        x, caches = _hybrid_decode(params, cfg, x, caches, pos)
    else:
        raise ValueError(cfg.family)

    x = rms_norm(shard_act(x, "hidden"), params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.adt),
                        preferred_element_type=jnp.float32)
    logits = shard_act(logits, "logits")[:, 0]
    if count_mask is not None:
        return logits, caches, counts
    return logits, caches


def _hybrid_decode(params, cfg: ModelConfig, x, caches, pos):
    B = x.shape[0]
    D = cfg.d_model
    emb0 = x
    k = cfg.hybrid_attn_every
    G, tail = cfg.hybrid_groups, cfg.hybrid_tail
    d2 = 2 * D
    cos2, sin2 = _rope_at(pos, d2 // cfg.n_heads, cfg.rope_theta)
    sp = params["shared_attn"]
    win = caches["k"].shape[2]
    # ring-buffer slot + valid-prefix length for the windowed shared cache
    wpos = pos % win
    vlen = jnp.minimum(pos + 1, win)

    blocks = params["blocks"]
    ckeys = ("h", "conv_x", "conv_b", "conv_c")
    grouped = jax.tree.map(lambda a: a[: G * (k - 1)].reshape((G, k - 1) + a.shape[1:]),
                           blocks)
    tail_blocks = jax.tree.map(lambda a: a[G * (k - 1):], blocks)
    m_states = tuple(caches[key] for key in ckeys)
    gm_states = jax.tree.map(lambda a: a[: G * (k - 1)].reshape((G, k - 1) + a.shape[1:]),
                             m_states)
    tl_states = jax.tree.map(lambda a: a[G * (k - 1):], m_states)

    def mamba_step(h, xs):
        blk, st = xs
        y, st = m2.mamba2_decode(blk["ssm"],
                                 rms_norm(h, blk["norm1"], cfg.norm_eps), st)
        return h + y, st

    def group_step(h, xs):
        grp, gst, kc, vc = xs
        h, new_m = jax.lax.scan(mamba_step, h, (grp, gst))
        z = jnp.concatenate([h, emb0], axis=-1)
        hn = rms_norm(z, sp["norm1"], cfg.norm_eps)
        a, (kc, vc) = attn.gqa_decode(sp["attn"], hn, kc, vc, pos, cos2, sin2,
                                      write_pos=wpos, valid_len=vlen)
        z = z + a
        hn = rms_norm(z, sp["norm2"], cfg.norm_eps)
        z = z + mlp_swiglu(sp["mlp"], hn)
        y = jnp.einsum("bse,ed->bsd", z, sp["out"].astype(z.dtype),
                       preferred_element_type=jnp.float32).astype(z.dtype)
        return h + y, (new_m, kc, vc)

    x, (new_gm, new_k, new_v) = jax.lax.scan(
        group_step, x, (grouped, gm_states, caches["k"], caches["v"])
    )
    x, new_tl = jax.lax.scan(mamba_step, x, (tail_blocks, tl_states))

    def _merge(a, b):
        return jnp.concatenate([a.reshape((-1,) + a.shape[2:]), b], axis=0)

    caches = {key: _merge(new_gm[i], new_tl[i]) for i, key in enumerate(ckeys)}
    caches["k"], caches["v"] = new_k, new_v
    return x, caches


# ===================================================================== loss
def lm_loss(
    params: dict[str, Any],
    cfg: ModelConfig,
    tokens: jax.Array,                       # (B, S)
    *,
    prefix_embeds: jax.Array | None = None,
    loss_mask: jax.Array | None = None,      # (B, S-1) over target positions
) -> jax.Array:
    """Next-token CE (+ router aux).  Targets are tokens shifted by one."""
    logits, _, aux = forward_full(params, cfg, tokens, prefix_embeds=prefix_embeds)
    Np = prefix_embeds.shape[1] if prefix_embeds is not None else 0
    text_logits = logits[:, Np:, :]
    pred = text_logits[:, :-1]
    tgt = tokens[:, 1:]
    ce = cross_entropy_loss(pred, tgt, vocab_size=cfg.vocab_size, mask=loss_mask)
    return ce + cfg.router_aux_weight * aux
