"""Mixture-of-Experts FFN with top-k routing: capacity dispatch for training,
dropless grouped dispatch for serving.

Used by olmoe (64 experts, top-8) and deepseek-v2 (2 shared + 160 routed,
top-6; V2-Lite 2 shared + 64 routed, top-6).  The capacity path
(:func:`moe_ffn`), in order of its design goals:

1.  **Linear in tokens.**  The dispatch is slot-scatter / slot-gather:
    every (token, choice) pair gets a slot ``expert·cap + position`` computed
    from a running per-expert count; tokens past an expert's capacity are
    dropped (their gate mass is simply lost, Switch-style).  Nothing of size
    (tokens × experts × capacity) is ever materialized.

2.  **EP-shardable.**  Expert weight stacks are (E, D, F) so the leading
    axis shards over the ``model`` mesh axis; the scatter/gather then
    induces the expected all-to-all under GSPMD.

3.  **Load-balance aux loss** (Switch/GShard form): ``E · Σ_e f_e · p_e``.

The serving path (:func:`moe_dropless`) loses no token: the (token, choice)
pairs are sorted by expert and each expert's SwiGLU runs over exactly the
rows it received (:func:`repro.kernels.grouped_matmul.grouped_matmul`), so a
batch that routes unevenly costs time and never gate mass.  The grouped
matmuls take the whole stack of the model's expert weights and the layer's
index, and read that layer's experts in place.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.layers import Initializer, he_init, mlp_swiglu
from repro.sharding.ctx import shard_act

__all__ = ["init_moe", "moe_ffn", "moe_dropless", "expert_counts"]


def init_moe(
    ini: Initializer,
    d_model: int,
    d_ff_expert: int,
    n_experts: int,
    *,
    n_shared: int = 0,
    dtype=jnp.float32,
) -> dict[str, Any]:
    E, D, F = n_experts, d_model, d_ff_expert
    p: dict[str, Any] = {
        "router": he_init(ini, (D, E), D, jnp.float32),  # router stays fp32
        "w_gate": he_init(ini, (E, D, F), D, dtype),
        "w_up": he_init(ini, (E, D, F), D, dtype),
        "w_down": he_init(ini, (E, F, D), F, dtype),
    }
    if n_shared:
        Fs = n_shared * d_ff_expert
        p["shared"] = {
            "w_gate": he_init(ini, (D, Fs), D, dtype),
            "w_up": he_init(ini, (D, Fs), D, dtype),
            "w_down": he_init(ini, (Fs, D), Fs, dtype),
        }
    return p


def _route(p: dict[str, Any], xt: jax.Array, k: int, norm_topk_prob: bool,
           routed_scaling_factor: float):
    """Softmax router in float32 at full precision, greedy top-``k``:
    (gates (T, E), chosen gates (T, k), chosen experts (T, k)).  The chosen
    gates are renormalised to sum to one only where ``norm_topk_prob``, then
    scaled by ``routed_scaling_factor``."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)
    if norm_topk_prob:
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
    return gates, top_g * routed_scaling_factor, top_i


def _count(top_i: jax.Array, n_experts: int,
           mask: jax.Array | None) -> jax.Array:
    """Routed choices per expert, over the tokens ``mask`` keeps."""
    w = (jnp.ones(top_i.shape, jnp.int32) if mask is None else
         jnp.broadcast_to(mask.reshape(-1, 1), top_i.shape).astype(jnp.int32))
    return jnp.zeros((n_experts,), jnp.int32).at[top_i.reshape(-1)].add(
        w.reshape(-1))


def expert_counts(p: dict[str, Any], x: jax.Array, *, k: int,
                  mask: jax.Array | None = None) -> jax.Array:
    """(E,) choices the router makes for the tokens of ``x`` (B, S, D) that
    ``mask`` (B, S) keeps: the expert load of the capacity path."""
    xt = x.reshape(-1, x.shape[-1])
    _, _, top_i = _route(p, xt, k, False, 1.0)
    return _count(top_i, p["router"].shape[-1], mask)


def moe_ffn(
    p: dict[str, Any],
    x: jax.Array,               # (B, S, D)
    *,
    k: int,
    capacity_factor: float = 1.25,
    norm_topk_prob: bool = True,
    routed_scaling_factor: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss scalar)."""
    B, S, D = x.shape
    E = p["router"].shape[-1]
    F = p["w_gate"].shape[-1]
    T = B * S
    cap = max(k, int(T * k * capacity_factor / E))
    cap = -(-cap // 4) * 4  # round up to a lane-friendly multiple
    dt = x.dtype

    xt = x.reshape(T, D)
    gates, top_g, top_i = _route(p, xt, k, norm_topk_prob,
                                 routed_scaling_factor)

    # ---- slot assignment: running count per expert, slot-priority order
    counts = jnp.zeros((E,), jnp.int32)
    slots = []
    keeps = []
    for j in range(k):
        onehot = jax.nn.one_hot(top_i[:, j], E, dtype=jnp.int32)        # (T, E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot                  # (T, E)
        pos = jnp.sum(pos_in_e * onehot, axis=-1) + counts[top_i[:, j]]  # (T,)
        keep = pos < cap
        # dropped copies land on slot 0 with a zero contribution (keeps the
        # buffer exactly (E·cap, D) — evenly shardable over the expert axis)
        slots.append(jnp.where(keep, top_i[:, j] * cap + pos, 0))
        keeps.append(keep)
        counts = counts + jnp.sum(onehot, axis=0)
    slot = jnp.stack(slots, 1)                                  # (T, k)
    keep = jnp.stack(keeps, 1)                                  # (T, k)

    # ---- dispatch: ONE scatter-add for all k token copies.  k separate
    # scatters would each force a full-buffer cross-data combine; one
    # scatter means one combine (EXPERIMENTS.md §Perf, olmoe hillclimb).
    contrib = (xt[:, None, :] * keep[..., None].astype(dt)).reshape(T * k, D)
    buf = jnp.zeros((E * cap, D), dt).at[slot.reshape(-1)].add(contrib)
    # hint the sharded layout at the scatter output itself so the cross-data
    # combine lowers to reduce-scatter (half the wire bytes of all-reduce)
    buf = shard_act(buf, "moe_buffer_flat")
    eb = shard_act(buf.reshape(E, cap, D), "moe_buffer")

    # ---- expert computation (batched SwiGLU over the expert axis)
    g = jnp.einsum("ecd,edf->ecf", eb, p["w_gate"].astype(dt),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", eb, p["w_up"].astype(dt),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)

    # ---- combine: ONE gather of every choice's slot output.  bf16 on
    # purpose: the gather/scatter pair is the EP boundary — keeping its
    # operands (and cotangents) in bf16 halves the cross-shard combine
    # traffic; the k-way weighted sum is numerically benign in bf16.
    gathered = eo.reshape(E * cap, D)[slot.reshape(-1)].reshape(T, k, D)
    w = (top_g * keep.astype(jnp.float32)).astype(dt)           # (T, k)
    out = jnp.einsum("tkd,tk->td", gathered, w)

    # ---- shared experts (deepseek): always-on dense SwiGLU
    if "shared" in p:
        out = out + mlp_swiglu(p["shared"], xt)

    # ---- aux loss: fraction dispatched (1st choice) × mean router prob
    f = jnp.mean(jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32), axis=0)
    pr = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(f * pr)
    return out.reshape(B, S, D), aux


def moe_dropless(
    p: dict[str, Any],
    x: jax.Array,               # (B, S, D)
    *,
    k: int,
    layer: jax.Array | int = 0,
    norm_topk_prob: bool = False,
    routed_scaling_factor: float = 1.0,
    count_mask: jax.Array | None = None,   # (B, S): tokens to count
) -> tuple[jax.Array, jax.Array]:
    """Dropless MoE: every token's ``k`` chosen experts, and only those.

    The T·k (token, choice) rows are sorted by expert; a grouped matmul runs
    each expert's SwiGLU over its own contiguous rows; the rows are put back
    in token order, weighted by their gates in float32 and summed; the
    shared experts are added.  No capacity, no token dropped.  The routed
    experts' weights are one layer's, (E, K, N), or the stack of every MoE
    layer's, (L, E, K, N), of which ``layer`` is this one: they are read in
    place, never sliced out.  Returns (output (B, S, D), routed choices per
    expert (E,) over the tokens ``count_mask`` keeps)."""
    B, S, D = x.shape
    E = p["router"].shape[-1]
    T = B * S
    dt = x.dtype
    xt = x.reshape(T, D)
    _, top_g, top_i = _route(p, xt, k, norm_topk_prob, routed_scaling_factor)
    flat_e = top_i.reshape(-1)                                  # (T·k,)
    order = jnp.argsort(flat_e, stable=True)
    rows = xt[order // k]                                       # sorted rows
    sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)

    def experts(rows, name):
        w = p[name]
        if w.ndim == 3:
            return grouped_matmul(rows, w[None], 0, sizes)
        return grouped_matmul(rows, w, layer, sizes)

    g = experts(rows, "w_gate")
    u = experts(rows, "w_up")
    h = (jax.nn.silu(g) * u).astype(dt)
    y = experts(h, "w_down")                                    # (T·k, D)
    back = jnp.argsort(order)                                   # unsort
    y = y[back].reshape(T, k, D)
    out = jnp.sum(y * top_g[..., None], axis=1)     # elementwise: float32
    if "shared" in p:
        out = out + mlp_swiglu(p["shared"], xt).astype(jnp.float32)
    return out.astype(dt).reshape(B, S, D), _count(top_i, E, count_mask)
