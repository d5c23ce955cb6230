"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU) + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import gemv as gemv_mod
from repro.kernels import ops, ref
from repro.kernels.linear_pipeline import fused_linear_chain

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------- spmv
@pytest.mark.parametrize("m,n,density", [
    (16, 24, 0.2), (100, 300, 0.1), (64, 64, 1.0), (33, 130, 0.4), (8, 8, 0.0),
])
@pytest.mark.parametrize("batch", [1, 5, 32])
def test_spmv_sweep(m, n, density, batch):
    w = RNG.normal(size=(m, n)).astype(np.float32)
    w[RNG.random((m, n)) >= density] = 0.0
    x = RNG.normal(size=(batch, n)).astype(np.float32)
    packed = ops.pack_bcsr(w, bm=16, bk=16)
    out = ops.spmv(packed, jnp.asarray(x))
    np.testing.assert_allclose(out, ref.spmv_ref(w, x), rtol=5e-4, atol=1e-4)


def test_spmv_density_accounting():
    w = np.zeros((64, 64), np.float32)
    w[:16, :16] = 1.0                    # exactly one 16×16 tile in 16
    packed = ops.pack_bcsr(w, bm=16, bk=16)
    assert packed.density == pytest.approx(1 / 16)


def test_spmv_skips_zero_tiles():
    """Packed representation must scale with nnz tiles, not dense size —
    the bandwidth saving that makes SpMV the paper's star kernel."""
    w = np.zeros((256, 256), np.float32)
    w[0, 0] = 1.0
    packed = ops.pack_bcsr(w, bm=32, bk=32)
    assert packed.data.shape[1] == 1      # J = 1 surviving tile per row block


# ------------------------------------------------------------------- gemv
@pytest.mark.parametrize("m,n", [(8, 8), (128, 128), (60, 200), (255, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemv_sweep(m, n, dtype):
    w = jnp.asarray(RNG.normal(size=(m, n)), dtype)
    x = jnp.asarray(RNG.normal(size=(4, n)), dtype)
    out = ops.gemv(w, x)
    refv = ref.gemv_ref(w.astype(jnp.float32), x.astype(jnp.float32))
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out.astype(jnp.float32), refv, rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(16, 16, 16), (64, 128, 32), (129, 65, 70)])
def test_matmul_sweep(shape):
    m, k, n = shape
    a = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    np.testing.assert_allclose(ops.matmul(a, b), a @ b, rtol=1e-4, atol=1e-4)
    bt = jnp.asarray(RNG.normal(size=(n, k)), jnp.float32)
    np.testing.assert_allclose(
        gemv_mod.matmul(a, bt, transpose_b=True), a @ bt.T, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- linear pipeline
_STAGE_POOL = ["scalar_mul", "tanh", "relu", "sigmoid", "exp",
               "add_vec", "sub_vec", "hadamard_vec"]


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.sampled_from(_STAGE_POOL), min_size=1, max_size=6),
    st.integers(1, 3),
)
def test_linear_chain_property(ops_list, bexp):
    B, n = 2 ** bexp, 48
    rng = np.random.default_rng(hash(tuple(ops_list)) % 2**31)
    x = jnp.asarray(rng.normal(size=(B, n)).astype(np.float32))
    stages = []
    for op in ops_list:
        if op == "scalar_mul":
            stages.append((op, float(rng.normal())))
        elif op.endswith("_vec"):
            stages.append((op, jnp.asarray(rng.normal(size=n).astype(np.float32))))
        else:
            stages.append((op, None))
    out = fused_linear_chain(x, stages, bb=16, bn=128)
    expect = ref.linear_chain_ref(x, stages)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_linear_chain_arr_operands():
    x = jnp.asarray(RNG.normal(size=(4, 32)).astype(np.float32))
    e0 = jnp.asarray(RNG.normal(size=(4, 32)).astype(np.float32))
    stages = [("hadamard_arr", 0), ("tanh", None), ("add_arr", 1)]
    extras = [e0, 2.0 * e0]
    out = fused_linear_chain(x, stages, extras)
    np.testing.assert_allclose(out, ref.linear_chain_ref(x, stages, extras),
                               rtol=1e-5)


# ----------------------------------------------- quantized linear pipeline
_Q_STAGE_POOL = ["q_scalar_mul", "q_unary", "q_add_vec", "q_sub_vec",
                 "q_hadamard_vec", "q_add_arr", "q_hadamard_arr"]


def _random_q_program(ops_list, rng, n, bits):
    """A well-formed random q-stage program with small scales/shifts."""
    from repro.kernels.linear_pipeline import fused_linear_chain_q

    qm = (1 << (bits - 1)) - 1
    stages, vecs, extras = [], [], []
    for op in ops_list:
        if op == "q_scalar_mul":
            stages.append((op, (int(rng.integers(-5, 6)),
                                int(rng.integers(-2, 4)))))
        elif op == "q_unary":
            stages.append((op, (str(rng.choice(["tanh", "sigmoid", "relu",
                                                "exp"])),
                                int(rng.integers(3, 7)),
                                int(rng.integers(3, 7)))))
        elif op.endswith("_vec"):
            vecs.append(rng.integers(-qm, qm + 1, size=n).astype(f"int{bits}"))
            if op == "q_hadamard_vec":
                stages.append((op, (len(vecs) - 1, int(rng.integers(1, 5)))))
            else:
                stages.append((op, (len(vecs) - 1, int(rng.integers(-2, 3)),
                                    int(rng.integers(-2, 3)),
                                    int(rng.integers(-1, 3)))))
        else:
            extras.append(None)       # placeholder, filled by the caller
            if op == "q_hadamard_arr":
                stages.append((op, (len(extras) - 1, int(rng.integers(1, 5)))))
            else:
                stages.append((op, (len(extras) - 1, int(rng.integers(-2, 3)),
                                    int(rng.integers(-2, 3)),
                                    int(rng.integers(-1, 3)))))
    return stages, vecs, len(extras)


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.sampled_from(_Q_STAGE_POOL), min_size=1, max_size=5),
    st.integers(0, 2),
    st.sampled_from([8, 16]),
)
def test_linear_chain_q_property(ops_list, bexp, bits):
    """The fixed-point pipeline kernel must match its pure-jnp oracle
    bitwise on random stage programs, shapes and both activation widths."""
    from repro.kernels.linear_pipeline import fused_linear_chain_q

    B, n = 2 ** bexp, 40
    rng = np.random.default_rng((hash(tuple(ops_list)) ^ bits) % 2**31)
    qm = (1 << (bits - 1)) - 1
    dt = f"int{bits}"
    stages, vecs, n_arr = _random_q_program(ops_list, rng, n, bits)
    x = jnp.asarray(rng.integers(-qm, qm + 1, size=(B, n)).astype(dt))
    extras = [jnp.asarray(rng.integers(-qm, qm + 1, size=(B, n)).astype(dt))
              for _ in range(n_arr)]
    vecs = [jnp.asarray(v) for v in vecs]
    out = fused_linear_chain_q(x, stages, vecs, extras, bits=bits,
                               bb=16, bn=128)
    expect = ref.linear_chain_q_ref(x, stages, vecs, extras, bits=bits)
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_linear_chain_q_matches_per_node_semantics():
    """A q-chain program lowered from real NodeQuant shifts must equal the
    per-node integer templates exactly (scalar_mul → requantize chain)."""
    from repro.core.quantize import requantize_i32
    from repro.kernels.linear_pipeline import fused_linear_chain_q

    x = jnp.asarray(np.arange(-64, 64, dtype=np.int8))
    # x at exp 5, scalar 3 at exp 4, out exp 5  => rq shift = 5 + 4 - 5 = 4
    out = fused_linear_chain_q(x, [("q_scalar_mul", (3, 4))], bits=8)
    expect = requantize_i32(x.astype(jnp.int32) * 3, 4, bits=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# -------------------------------------------------- decode attention oracle
def test_decode_attention_ref_vs_plain():
    from repro.models.attention import plain_attention

    B, S, H, KV, D = 2, 16, 8, 4, 16
    q = jnp.asarray(RNG.normal(size=(B, 1, H, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, KV, D)).astype(np.float32))
    lens = jnp.asarray([5, 16], jnp.int32)
    out = ref.decode_attention_ref(q[:, 0], k, v, lens)
    # reference via plain attention with q at position len-1
    for b in range(B):
        L = int(lens[b])
        pa = plain_attention(q[b:b+1], k[b:b+1, :L], v[b:b+1, :L],
                             causal=True, q_offset=L - 1)
        np.testing.assert_allclose(out[b], pa[0, 0], rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- mamba2 ssd
@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.sampled_from([8, 24, 32]), st.integers(1, 4))
def test_ssd_chunked_vs_sequential(b, s, h):
    from repro.models.mamba2 import ssd_chunked

    P, N = 8, 8
    rng = np.random.default_rng(b * 100 + s + h)
    x = jnp.asarray(rng.normal(size=(b, s, h, P)).astype(np.float32))
    a = jnp.asarray(-np.abs(rng.normal(size=(b, s, h))).astype(np.float32) * 0.4)
    bb = jnp.asarray(rng.normal(size=(b, s, N)).astype(np.float32) * 0.4)
    cc = jnp.asarray(rng.normal(size=(b, s, N)).astype(np.float32) * 0.4)
    y, _ = ssd_chunked(x, a, bb, cc, chunk=8)
    y_ref = ref.mamba2_ssd_ref(x, a, bb, cc)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


def test_ssd_initial_state_threading():
    from repro.models.mamba2 import ssd_chunked

    rng = np.random.default_rng(3)
    B, S, H, P, N = 1, 24, 2, 4, 4
    x = jnp.asarray(rng.normal(size=(B, S, H, P)).astype(np.float32))
    a = jnp.asarray(-np.abs(rng.normal(size=(B, S, H))).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32) * 0.3)
    c = jnp.asarray(rng.normal(size=(B, S, N)).astype(np.float32) * 0.3)
    # split run must equal full run when the state is threaded through
    y_full, h_full = ssd_chunked(x, a, b, c, chunk=8)
    y1, h1 = ssd_chunked(x[:, :16], a[:, :16], b[:, :16], c[:, :16], chunk=8)
    y2, h2 = ssd_chunked(x[:, 16:], a[:, 16:], b[:, 16:], c[:, 16:], chunk=8, h0=h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h2, h_full, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------ fused flash attention
@pytest.mark.parametrize("B,S,H,KV,dh", [
    (2, 64, 4, 4, 32), (1, 100, 8, 2, 64), (2, 33, 4, 1, 128), (1, 16, 2, 2, 256),
])
def test_fused_flash_attention_vs_plain(B, S, H, KV, dh):
    from repro.kernels.flash_attention import flash_attention_fused
    from repro.models.attention import plain_attention

    q = jnp.asarray(RNG.normal(size=(B, S, H, dh)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    out = flash_attention_fused(q, k, v, causal=True, bq=32, bk=32)
    ref = plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_fused_flash_non_causal():
    from repro.kernels.flash_attention import flash_attention_fused
    from repro.models.attention import plain_attention

    q = jnp.asarray(RNG.normal(size=(1, 40, 4, 32)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 40, 4, 32)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 40, 4, 32)).astype(np.float32))
    out = flash_attention_fused(q, k, v, causal=False, bq=16, bk=16)
    ref = plain_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- decode attention
@pytest.mark.parametrize("B,S,H,KV,dh", [
    (2, 64, 8, 4, 32), (3, 100, 4, 1, 64), (1, 32, 16, 2, 128),
])
def test_decode_attention_kernel_vs_ref(B, S, H, KV, dh):
    from repro.kernels.decode_attention import decode_attention

    q = jnp.asarray(RNG.normal(size=(B, H, dh)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    lens = jnp.asarray(RNG.integers(1, S + 1, size=B), jnp.int32)
    out = decode_attention(q, k, v, lens, bk=16)
    expect = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_decode_attention_kernel_full_lengths():
    from repro.kernels.decode_attention import decode_attention

    B, S, H, KV, dh = 2, 48, 4, 4, 32
    q = jnp.asarray(RNG.normal(size=(B, H, dh)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, S, KV, dh)).astype(np.float32))
    lens = jnp.full((B,), S, jnp.int32)
    out = decode_attention(q, k, v, lens, bk=16)
    expect = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- grouped matmul
def _sizes(case: str, m: int, e: int) -> np.ndarray:
    if case == "one_expert":
        return np.eye(e, dtype=np.int32)[e // 2] * m
    sizes = np.bincount(RNG.integers(0, e, m), minlength=e).astype(np.int32)
    if case == "empty_experts":         # three experts no row chose
        sizes[[0, 3, e - 1]] = 0
        sizes[1] += m - sizes.sum()
    return sizes


@pytest.mark.parametrize("case,m", [
    ("empty_experts", 300),
    ("one_expert", 256),
    ("tail_tile", 333),     # 2 whole 128-row tiles and a part of one
    ("decode", 128 * 6),    # 128 slots, top-6
    ("prefill", 256 * 6),   # a 256-token prompt, top-6
])
def test_grouped_matmul_reads_each_layer_of_the_stack(case, m):
    """Each layer of a 3-layer expert stack, read in place, gives what a
    grouped matmul on that layer's sliced weights gives, and a float64
    reference."""
    from repro.kernels.grouped_matmul import grouped_matmul

    L, E, K, N = 3, 8, 64, 40
    sizes = _sizes(case, m, E)
    assert sizes.sum() == m
    lhs = RNG.normal(size=(m, K)).astype(np.float32)
    rhs = RNG.normal(size=(L, E, K, N)).astype(np.float32)
    expert = np.repeat(np.arange(E), sizes)
    for layer in range(L):
        got = np.asarray(grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                        layer, jnp.asarray(sizes)))
        sliced = jax.lax.ragged_dot(jnp.asarray(lhs), jnp.asarray(rhs[layer]),
                                    jnp.asarray(sizes),
                                    precision=jax.lax.Precision.HIGHEST)
        want = np.einsum("mk,mkn->mn", lhs.astype(np.float64),
                         rhs[layer][expert].astype(np.float64))
        np.testing.assert_allclose(got, np.asarray(sliced), rtol=1e-5,
                                   atol=1e-4)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
