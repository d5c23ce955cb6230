"""Operations and bytes of the token path's programs, from the published
config keys of a DeepSeek-V2-style model (MLA, a leading dense layer, then
routed and shared experts).

Counted for real tokens and real cache rows only: a prompt's padding to its
bucket and the cache rows past each slot's position are waste.  Operations
are two per multiply-add of the model's matmuls (attention scores and
values included); norms, softmax and activations are left out.  Bytes are
the least the program has to move: each weight it uses once (bf16 here,
``itemsize``), the routed experts that the step's choices can reach, the
embedding rows of its tokens, the cache rows it reads and writes.

* ``decode(shape, n_active, ctx_rows)``: one step of ``lm_decode``, the
  absorbed form (queries taken into the latent space, attention over the
  latent cache rows ``0..pos`` of each active slot; ``ctx_rows`` is their
  sum over the slots), the head at every active slot.
* ``prefill(shape, plen)``: ``lm_prefill`` of one prompt, keys and values
  made per head from the latent, causal attention, the head at the last
  position only.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int              # hidden_size
    heads: int
    nope: int           # qk_nope_head_dim
    rope: int           # qk_rope_head_dim
    v: int              # v_head_dim
    rank: int           # kv_lora_rank
    vocab: int
    n_dense: int        # first_k_dense_replace
    n_moe: int
    d_ff: int           # intermediate_size
    d_expert: int       # moe_intermediate_size
    experts: int        # n_routed_experts
    k: int              # num_experts_per_tok
    shared: int         # n_shared_experts
    itemsize: int = 2

    @property
    def layers(self) -> int:
        return self.n_dense + self.n_moe

    @property
    def cache_row_bytes(self) -> int:
        """One token's cache in one layer: the latent and the rope key."""
        return (self.rank + self.rope) * self.itemsize


def shape(cfg: dict) -> Shape:
    """The shape of a configuration file's model."""
    n_dense = int(cfg["first_k_dense_replace"])
    return Shape(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        vocab=cfg["vocab_size"], n_dense=n_dense,
        n_moe=cfg["num_hidden_layers"] - n_dense,
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"])


def _proj(s: Shape) -> int:
    """Attention weights a token's own projections use in one layer: its
    query (nope and rope), its latent and rope key, and W_o."""
    return (s.d * s.heads * (s.nope + s.rope) + s.d * (s.rank + s.rope)
            + s.heads * s.v * s.d)


def _expert(s: Shape, width: int) -> int:
    return 3 * s.d * width


def _reached(s: Shape, choices: int) -> float:
    """Routed experts of one layer that ``choices`` uniform picks reach, in
    expectation: the weights a step has to read."""
    return s.experts * (1.0 - (1.0 - 1.0 / s.experts) ** choices)


def _ffn_token_ops(s: Shape) -> float:
    dense = 2 * _expert(s, s.d_ff)
    moe = 2 * ((s.k + s.shared) * _expert(s, s.d_expert) + s.d * s.experts)
    return s.n_dense * dense + s.n_moe * moe


def _weight_bytes(s: Shape, choices_per_layer: int) -> float:
    """Every weight but the embedding, the routed experts the step's
    choices reach, at ``itemsize`` (the router is float32)."""
    attn = s.layers * (_proj(s) + s.rank * s.heads * (s.nope + s.v))
    dense = s.n_dense * _expert(s, s.d_ff)
    moe = s.n_moe * (_reached(s, choices_per_layer) * _expert(s, s.d_expert)
                     + s.shared * _expert(s, s.d_expert))
    router = s.n_moe * s.d * s.experts * 4 / s.itemsize
    return (attn + dense + moe + router + s.d * s.vocab) * s.itemsize


def decode(s: Shape, n_active: int, ctx_rows: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step."""
    if n_active == 0:
        return 0.0, 0.0
    absorb = s.heads * s.nope * s.rank + s.heads * s.rank * s.v  # W_uk, W_uv
    per_token = (2 * s.layers * (_proj(s) + absorb) + _ffn_token_ops(s)
                 + 2 * s.d * s.vocab)
    # scores over latent and rope key, then the latent context, per row
    per_row = 2 * s.heads * (2 * s.rank + s.rope)
    ops = n_active * per_token + s.layers * ctx_rows * per_row
    byt = (_weight_bytes(s, n_active * s.k)
           + n_active * s.d * s.itemsize                     # embedding rows
           + s.layers * (ctx_rows + n_active) * s.cache_row_bytes)
    return float(ops), float(byt)


def prefill(s: Shape, plen: int) -> tuple[float, float]:
    """(operations, bytes) of one prompt's prefill."""
    up = s.rank * s.heads * (s.nope + s.v)      # keys and values per head
    per_token = 2 * s.layers * (_proj(s) + up) + _ffn_token_ops(s)
    # causal: query i attends over i + 1 keys, scores and values
    pairs = plen * (plen + 1) // 2
    attn = 2 * s.layers * s.heads * (s.nope + s.rope + s.v) * pairs
    ops = plen * per_token + attn + 2 * s.d * s.vocab
    byt = (_weight_bytes(s, plen * s.k) + plen * s.d * s.itemsize
           + s.layers * plen * s.cache_row_bytes)
    return float(ops), float(byt)
