"""DeepSeek-V2's published checkpoint with random weights, and a plain
reference of its forward pass over that checkpoint, for checking the served
model: float32 ``jax.numpy``, no cache, no batching, no kernels, no
capacity.  It imports nothing of the program.

Everything comes from the configuration's published ``config.json`` keys
(``spec``): the number of layers, which of them are dense, the experts and
their widths, the heads.  The checkpoint (``weights``) holds every tensor
under its name and in its layout in ``modeling_deepseek.py`` of
``deepseek-ai/DeepSeek-V2-Lite`` (``torch.nn.Linear`` weights are
(out, in)); each tensor is drawn from the seed and its own name, so any one
of them can be drawn again alone: linear weights N(0, 1/in), the embedding
N(0, 0.02²), norm weights 1 + N(0, 0.1²), stored in the configuration's
``dtype``.  Expert ``j``'s tensors (``mlp.experts.{j}.*``) are drawn each
from its own name and handed out stacked on a leading expert axis.

The forward follows ``modeling_deepseek.py``:

* MLA without q-LoRA: q = ``q_proj``(x) split per head into nope and rope
  parts; ``kv_a_proj_with_mqa``(x) split into the latent c and one shared
  rope key; c through ``kv_a_layernorm``, then ``kv_b_proj`` into each
  head's nope key and value; causal softmax attention with scale
  ``(nope + rope)^-½ · mscale(factor, mscale_all_dim)²``; out through
  ``o_proj``.
* RoPE on the interleaved pairs of the rope part (the published code
  gathers even then odd dimensions and rotates the two halves), with YaRN
  ``rope_scaling``: frequencies blended between ``θ^-2i/d`` and that ÷
  ``factor`` by a linear ramp over the dimensions between the ``beta_fast``
  and ``beta_slow`` correction dimensions of
  ``original_max_position_embeddings``; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
* Layer ``i`` is MoE where ``i >= first_k_dense_replace`` and ``i %
  moe_layer_freq == 0``, else a SwiGLU of ``intermediate_size``.  MoE: a
  float32 softmax router over ``n_routed_experts``, greedy
  top-``num_experts_per_tok``, the chosen gates renormalised where
  ``norm_topk_prob``, otherwise times ``routed_scaling_factor``; every
  expert is evaluated on every token and weighted by its gate (zero where
  not chosen); plus ``n_shared_experts`` shared experts as one SwiGLU of
  ``n_shared_experts · moe_intermediate_size``.
* RMSNorm with ``rms_norm_eps`` before attention, FFN and the untied head.

The forward draws each layer's tensors when it reaches the layer and runs
one jitted program per layer kind, with attention in blocks of queries and
the experts one at a time, so that it fits beside a serving engine on one
chip.  ``rounding="float8_e4m3fn"`` rounds every weight and every matmul
operand to that type, with a per-tensor scale (the control: one precision
below bfloat16).
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["spec", "layout", "weights", "forward", "yarn_freqs",
           "yarn_mscale"]

F32 = jnp.float32
QUERY_BLOCK = 512
NORM_SPREAD = 0.1
EMBED_SCALE = 0.02
SERVED = {"model_type": "deepseek_v2", "hidden_act": "silu",
          "scoring_func": "softmax", "topk_method": "greedy",
          "q_lora_rank": None, "attention_bias": False,
          "tie_word_embeddings": False}
KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "moe_layer_freq", "intermediate_size", "moe_intermediate_size",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank", "rms_norm_eps", "rope_theta", "rope_scaling",
        "vocab_size", "dtype")


def spec(cfg: dict) -> dict:
    """The published keys the reference reads; a mechanism it does not
    implement is an error."""
    for k, v in SERVED.items():
        if cfg[k] != v:
            raise ValueError(f"{k}={cfg[k]!r}: the reference implements "
                             f"{v!r}")
    rs = cfg.get("rope_scaling")
    if rs and rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs['type']!r}")
    return {k: cfg[k] for k in KEYS}


def is_moe(s: dict, i: int) -> bool:
    return (s["n_routed_experts"] > 0 and i >= s["first_k_dense_replace"]
            and i % s["moe_layer_freq"] == 0)


def layout(s: dict, layer: int | None = None) -> dict:
    """Published name -> (shape, kind) of the model's global tensors, or of
    layer ``layer``'s under names relative to ``model.layers.<layer>.``.
    An expert tensor's name holds ``{j}`` and its shape a leading expert
    axis."""
    D, V = s["hidden_size"], s["vocab_size"]
    if layer is None:
        return {"model.embed_tokens.weight": ((V, D), "embed"),
                "model.norm.weight": ((D,), "norm"),
                "lm_head.weight": ((V, D), "linear")}
    H, R = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    out = {"input_layernorm.weight": ((D,), "norm"),
           "post_attention_layernorm.weight": ((D,), "norm"),
           "self_attn.q_proj.weight": ((H * (dn + dr), D), "linear"),
           "self_attn.kv_a_proj_with_mqa.weight": ((R + dr, D), "linear"),
           "self_attn.kv_a_layernorm.weight": ((R,), "norm"),
           "self_attn.kv_b_proj.weight": ((H * (dn + dv), R), "linear"),
           "self_attn.o_proj.weight": ((D, H * dv), "linear")}

    def swiglu(prefix, width, lead=()):
        return {f"{prefix}gate_proj.weight": (lead + (width, D), "linear"),
                f"{prefix}up_proj.weight": (lead + (width, D), "linear"),
                f"{prefix}down_proj.weight": (lead + (D, width), "linear")}

    if not is_moe(s, layer):
        out.update(swiglu("mlp.", s["intermediate_size"]))
        return out
    E, F = s["n_routed_experts"], s["moe_intermediate_size"]
    out["mlp.gate.weight"] = ((E, D), "linear")
    out.update(swiglu("mlp.experts.{j}.", F, (E,)))
    if s["n_shared_experts"]:
        out.update(swiglu("mlp.shared_experts.",
                          s["n_shared_experts"] * F))
    return out


def _key(seed: int, name: str) -> jax.Array:
    return jax.random.fold_in(jax.random.key(seed),
                              np.uint32(zlib.crc32(name.encode())))


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _draw(key, shape, kind, dtype):
    z = jax.random.normal(key, shape, F32)
    if kind == "norm":
        w = 1.0 + NORM_SPREAD * z
    elif kind == "embed":
        w = EMBED_SCALE * z
    else:                           # (out, in): the input is the last axis
        w = z / math.sqrt(shape[-1])
    return w.astype(dtype)


def _draw_named(seed: int, name: str, shape: tuple, kind: str, dtype):
    if "{j}" not in name:
        return _draw(_key(seed, name), shape, kind, dtype)
    keys = jnp.stack([_key(seed, name.format(j=j)) for j in range(shape[0])])
    return jax.vmap(lambda k: _draw(k, shape[1:], kind, dtype))(keys)


def weights(s: dict, seed: int, layer: int | None = None) -> dict:
    """The checkpoint's global tensors, or layer ``layer``'s (names as
    ``layout`` gives them), drawn from ``seed``."""
    prefix = "" if layer is None else f"model.layers.{layer}."
    dtype = jnp.dtype(s["dtype"])
    return {n: _draw_named(seed, prefix + n, shape, kind, dtype)
            for n, (shape, kind) in layout(s, layer).items()}


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, rs: dict | None) -> np.ndarray:
    """Inverse frequencies of the rope dimensions, YaRN-blended."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs:
        return extra.astype(np.float32)
    factor, orig = float(rs["factor"]), float(
        rs["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra_share = 1.0 - ramp
    return (extra / factor * (1.0 - extra_share)
            + extra * extra_share).astype(np.float32)


def _rounder(rounding: str | None):
    if rounding is None:
        return lambda a: a.astype(F32)
    dt = jnp.dtype(rounding)
    top = float(jnp.finfo(dt).max)

    def r(a):
        a = a.astype(F32)
        s = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * s).astype(dt).astype(F32) / s
    return r


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, cos, sin):
    """x (S, ..., d): the published rotation, interleaved pairs gathered
    into halves (even dimensions, then odd) and the halves rotated."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    h = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (2 * h,)
    return x * cos.reshape(shape) + rot * sin.reshape(shape)


def _linear(x, w, r):
    """x @ w.T for a published (out, in) weight."""
    return jnp.matmul(r(x), r(w).T, precision="highest")


def _swiglu(x, w, prefix, r):
    g = _linear(x, w[prefix + "gate_proj.weight"], r)
    u = _linear(x, w[prefix + "up_proj.weight"], r)
    return _linear(jax.nn.silu(g) * u, w[prefix + "down_proj.weight"], r)


def _attention(h, w, s, r):
    S = h.shape[0]
    H, R = s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    ein = functools.partial(jnp.einsum, precision="highest")
    rs = s.get("rope_scaling")
    freqs = jnp.asarray(yarn_freqs(dr, s["rope_theta"], rs))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    m = 1.0
    scale = (dn + dr) ** -0.5
    if rs:
        m = (yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
             / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))
        if rs.get("mscale_all_dim"):
            scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    q = _linear(h, w["self_attn.q_proj.weight"], r).reshape(S, H, dn + dr)
    kv_a = _linear(h, w["self_attn.kv_a_proj_with_mqa.weight"], r)
    c = _norm(kv_a[:, :R], w["self_attn.kv_a_layernorm.weight"],
              s["rms_norm_eps"])
    kv = _linear(c, w["self_attn.kv_b_proj.weight"], r).reshape(
        S, H, dn + dv)
    q_rope = _rope(q[..., dn:], cos, sin)
    k_rope = _rope(kv_a[:, R:], cos, sin)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (S, H, dr))], -1)
    v = kv[..., dn:]
    qb = q.reshape(S // QUERY_BLOCK, QUERY_BLOCK, H, dn + dr)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        sc = ein("qhk,shk->hqs", r(qi), r(k)) * scale
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return ein("hqs,shk->qhk", r(p), r(v))

    out = jax.lax.map(block, (jnp.arange(S // QUERY_BLOCK), qb))
    return _linear(out.reshape(S, H * dv), w["self_attn.o_proj.weight"], r)


def _moe(x, w, s, r):
    k = s["num_experts_per_tok"]
    E = s["n_routed_experts"]
    gates = jax.nn.softmax(_linear(x, w["mlp.gate.weight"], r), axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)
    if k > 1 and s["norm_topk_prob"]:
        top_g = top_g / (jnp.sum(top_g, -1, keepdims=True) + 1e-20)
    else:
        top_g = top_g * s["routed_scaling_factor"]
    weight = jnp.zeros_like(gates).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_g)     # (S, E)
    names = [f"mlp.experts.{{j}}.{p}_proj.weight"
             for p in ("gate", "up", "down")]

    def expert(e, acc):
        one = {n: w[n][e] for n in names}
        y = _swiglu(x, one, "mlp.experts.{j}.", r)
        return acc + jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1) * y

    out = jax.lax.fori_loop(0, E, expert, jnp.zeros_like(x))
    if s["n_shared_experts"]:
        out = out + _swiglu(x, w, "mlp.shared_experts.", r)
    return out


@functools.partial(jax.jit, static_argnames=("spec_items", "rounding"))
def _layer(w, h, spec_items, rounding):
    s = _thaw(spec_items)
    r = _rounder(rounding)
    eps = s["rms_norm_eps"]
    h = h + _attention(_norm(h, w["input_layernorm.weight"], eps), w, s, r)
    x = _norm(h, w["post_attention_layernorm.weight"], eps)
    if "mlp.gate.weight" in w:
        return h + _moe(x, w, s, r)
    return h + _swiglu(x, w, "mlp.", r)


@functools.partial(jax.jit, static_argnames=("eps", "rounding"))
def _head(h, positions, norm, head, eps, rounding):
    r = _rounder(rounding)
    return _linear(_norm(h[positions], norm, eps), head, r)


def _frozen(s: dict) -> tuple:
    """The spec as a hashable static argument (one level of nesting)."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in s.items()))


def _thaw(items: tuple) -> dict:
    return {k: dict(v) if k == "rope_scaling" and v else v for k, v in items}


def forward(s: dict, seed: int, tokens, positions,
            rounding: str | None = None,
            length: int | None = None) -> jax.Array:
    """Float32 logits (len(positions), vocab) of the sequence ``tokens`` at
    ``positions``, from the checkpoint ``weights(s, seed)``.  ``tokens`` is
    padded at the end to ``length`` (a multiple of the query block), or
    where None to the next multiple of it: attention is causal, so padding
    changes nothing before it, and one ``length`` compiles each program
    once."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    S = -(-n // QUERY_BLOCK) * QUERY_BLOCK if length is None else length
    if S < n or S % QUERY_BLOCK:
        raise ValueError(f"length {S} for {n} tokens: want a multiple of "
                         f"{QUERY_BLOCK} at least as long")
    padded = np.zeros(S, np.int32)
    padded[:n] = tokens
    items = _frozen(s)
    top = weights(s, seed)
    with jax.default_matmul_precision("highest"):
        h = jnp.take(top["model.embed_tokens.weight"], jnp.asarray(padded),
                     axis=0).astype(F32)
        for i in range(s["num_hidden_layers"]):
            h = _layer(weights(s, seed, i), h, items, rounding)
        return _head(h, jnp.asarray(positions, jnp.int32),
                     top["model.norm.weight"], top["lm_head.weight"],
                     float(s["rms_norm_eps"]), rounding)
