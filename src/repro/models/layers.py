"""Common neural-net layers for the assigned-architecture stack.

Everything is a pure function over explicit parameter pytrees (no framework
module system): ``init_*`` builds the parameter subtree, the matching apply
function consumes it.  All matmuls run in the configured activation dtype
(bf16 by default) with fp32 accumulation via ``preferred_element_type``;
norms/softmax/CE statistics are fp32.

Sharding is *not* decided here — the planner (:mod:`repro.sharding.planner`)
attaches PartitionSpecs to the parameter tree by path; these layers only keep
tensor layouts stable and shard-friendly (heads-last attention weights,
(E, D, F) expert stacks, vocab-padded embeddings).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Initializer", "he_init", "rms_norm", "init_linear", "linear",
    "init_mlp", "mlp_swiglu", "YaRN", "rope_freqs", "rope_table",
    "apply_rope",
    "cross_entropy_loss", "pad_vocab", "ACT_DTYPE",
]

ACT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------- utils
def pad_vocab(vocab_size: int, multiple: int = 256) -> int:
    """Pad the vocabulary so embedding/logits shard evenly over the mesh."""
    return ((vocab_size + multiple - 1) // multiple) * multiple


@dataclasses.dataclass
class Initializer:
    """Deterministic splitting initializer (cheap, fold_in-based)."""

    key: jax.Array
    count: int = 0

    def next_key(self) -> jax.Array:
        self.count += 1
        return jax.random.fold_in(self.key, self.count)

    def normal(self, shape: tuple[int, ...], scale: float, dtype=jnp.float32) -> jax.Array:
        return (jax.random.normal(self.next_key(), shape, jnp.float32) * scale).astype(dtype)


def he_init(ini: Initializer, shape: tuple[int, ...], fan_in: int, dtype=jnp.float32) -> jax.Array:
    return ini.normal(shape, 1.0 / np.sqrt(max(1, fan_in)), dtype)


# ------------------------------------------------------------------- rms norm
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 statistics; returns in ``x.dtype``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- linear
def init_linear(ini: Initializer, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32) -> dict[str, jax.Array]:
    p = {"w": he_init(ini, (d_in, d_out), d_in, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    y = jnp.einsum(
        "...d,df->...f", x, p["w"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# -------------------------------------------------------------------- SwiGLU
def init_mlp(ini: Initializer, d_model: int, d_ff: int, dtype=jnp.float32) -> dict[str, Any]:
    return {
        "w_gate": he_init(ini, (d_model, d_ff), d_model, dtype),
        "w_up": he_init(ini, (d_model, d_ff), d_model, dtype),
        "w_down": he_init(ini, (d_ff, d_model), d_ff, dtype),
    }


def mlp_swiglu(p: dict[str, Any], x: jax.Array) -> jax.Array:
    dt = x.dtype
    g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(dt),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(dt),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


# ----------------------------------------------------------------------- RoPE
@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN context extension of RoPE, as DeepSeek-V2 configures it
    (``rope_scaling`` with ``type: yarn``): frequencies blended between
    interpolated (÷ ``factor``) and original by a linear ramp over the
    dimensions whose wavelengths lie between ``beta_fast`` and ``beta_slow``
    rotations of ``original_max_position``; cos/sin scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, and the
    attention's softmax scale by ``mscale(factor, mscale_all_dim)²``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def get_mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        return (self.get_mscale(self.factor, self.mscale)
                / self.get_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_factor(self) -> float:
        if not self.mscale_all_dim:
            return 1.0
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2

    def _correction_dim(self, rotations: float, dim: int, theta: float):
        return (dim * math.log(self.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    def freqs(self, dim: int, theta: float) -> np.ndarray:
        half = dim // 2
        extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        inter = extra / self.factor
        low = max(math.floor(self._correction_dim(self.beta_fast, dim,
                                                  theta)), 0)
        high = min(math.ceil(self._correction_dim(self.beta_slow, dim,
                                                  theta)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
        keep = 1.0 - ramp                  # 1: original frequency
        return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_freqs(dim: int, theta: float = 1e4,
               scaling: YaRN | None = None) -> jax.Array:
    """(dim/2,) inverse frequencies, YaRN-blended where ``scaling``."""
    if scaling is not None:
        return jnp.asarray(scaling.freqs(dim, theta))
    half = dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_table(seq_len: int, dim: int, theta: float = 1e4,
               offset: int = 0, scaling: YaRN | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """(seq_len, dim/2) cos/sin tables starting at absolute position
    ``offset`` (scaled by YaRN's cos/sin factor where ``scaling``)."""
    freqs = rope_freqs(dim, theta, scaling)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]
    if scaling is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = scaling.cos_sin_scale
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs; ``x``: (..., S, H, dim), tables: (S, dim/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :].astype(jnp.float32)
    s = sin[..., :, None, :].astype(jnp.float32)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


# ------------------------------------------------------------- cross entropy
def cross_entropy_loss(
    logits: jax.Array,       # (B, S, Vp) — possibly vocab-padded
    targets: jax.Array,      # (B, S) int32
    *,
    vocab_size: int,         # logical vocab; padded columns masked out
    mask: jax.Array | None = None,  # (B, S) 1.0 = count this position
) -> jax.Array:
    lf = logits.astype(jnp.float32)
    vp = lf.shape[-1]
    if vp != vocab_size:
        col = jnp.arange(vp)
        lf = jnp.where(col[None, None, :] < vocab_size, lf, -1e30)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
