"""The token path: a DeepSeek-V2-style model (its published ``config.json``
keys in the configuration file) served by the program's ``ServeEngine``
(slots over an MLA latent cache, bucketed prefill, one batched decode step
at a time), driven through ``submit`` / ``step`` by a traffic kind of
``loadgen``.

The weights are the published checkpoint's tensors, drawn from the
configuration's seed (``models/deepseek_v2.py``), and ``load_params`` maps
them into the program's parameter tree: every tensor has one place there
and every leaf of the tree is filled, at the shape the program gives it, or
set-up fails.  So a layer, an expert or a width the program dropped or got
wrong stops the run instead of passing unseen.

``correct`` compares logits, not sampled tokens: for a seeded sample of the
answered requests the engine keeps the logits it sampled the first token
and the first ``capture_steps`` decoded tokens from, and the plain reference
(float32 at full matmul precision, on the chip) computes its full forward
over the prompt and the served tokens from the same checkpoint.  The
control is that reference with every weight and matmul operand rounded to
float8 e4m3, one precision below the configuration's bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib

import numpy as np

from harness import lm_work, session, trace
from harness.peaks import peaks
from harness.session import CLOCK
from models import deepseek_v2 as ref
# the program's token path; a checkout without it fails here, before set-up
from repro.models.layers import YaRN
from repro.models.transformer import ModelConfig, init_params
from repro.serve.engine import DECODE_FIELDS, PREFILL_FIELDS, ServeEngine

LEAD_S = 0.05        # least time from the end of set-up to the window
GRACE_S = 120.0      # how long past the window's close requests may run
POOL_TOKENS = 1 << 22


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file's published
    keys; a mechanism the program does not have is an error."""
    ref.spec(cfg)                   # what the reference implements
    want = {"moe_layer_freq": 1, "n_group": 1, "topk_group": 1}
    for k, v in want.items():
        if cfg[k] != v:
            raise ValueError(f"{k}={cfg[k]!r}: the token path serves {v!r}")
    if cfg["qk_nope_head_dim"] != cfg["v_head_dim"]:
        raise ValueError("the program's MLA keeps v_head_dim = "
                         "qk_nope_head_dim")
    rs = cfg.get("rope_scaling")
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["qk_nope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        n_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        d_ff_expert=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_impl="dropless", use_mla=True,
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
        d_rope=cfg["qk_rope_head_dim"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=None if not rs else YaRN(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        act_dtype=cfg["dtype"], param_dtype=cfg["dtype"],
        vocab_pad_multiple=1, remat=False)


def _program_layer(w: dict, s: dict) -> dict:
    """One layer of the published checkpoint in the program's layout.  The
    rope columns of ``q_proj`` and ``kv_a_proj_with_mqa`` are gathered even
    then odd, so that the program's rotation of halves is the published
    rotation of interleaved pairs."""
    import jax.numpy as jnp

    w = dict(w)
    take = w.pop
    D, H, R = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    pairs = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    q = take("self_attn.q_proj.weight").reshape(H, dn + dr, D)
    kv_a = take("self_attn.kv_a_proj_with_mqa.weight")
    kv_b = take("self_attn.kv_b_proj.weight").reshape(H, dn + dv, R)
    blk = {"norm1": take("input_layernorm.weight"),
           "norm2": take("post_attention_layernorm.weight"),
           "attn": {
               "w_uq": q[:, :dn].transpose(2, 0, 1),
               "w_qr": q[:, dn:][:, pairs].transpose(2, 0, 1),
               "w_dkv": kv_a[:R].T,
               "w_kr": kv_a[R:][pairs].T,
               "norm_kv": take("self_attn.kv_a_layernorm.weight"),
               "w_uk": kv_b[:, :dn].transpose(2, 0, 1),
               "w_uv": kv_b[:, dn:].transpose(2, 0, 1),
               "wo": take("self_attn.o_proj.weight").reshape(
                   D, H, dv).transpose(1, 2, 0)}}

    def swiglu(prefix, stacked=False):
        t = (lambda a: jnp.swapaxes(a, 1, 2)) if stacked else (lambda a: a.T)
        return {f"w_{p}": t(take(f"{prefix}{p}_proj.weight"))
                for p in ("gate", "up", "down")}

    if "mlp.gate.weight" in w:
        blk["moe"] = {"router": take("mlp.gate.weight").T,
                      **swiglu("mlp.experts.{j}.", stacked=True)}
        if "mlp.shared_experts.gate_proj.weight" in w:
            blk["moe"]["shared"] = swiglu("mlp.shared_experts.")
    else:
        blk["mlp"] = swiglu("mlp.")
    if w:
        raise ValueError(f"checkpoint tensors the program has no place for: "
                         f"{sorted(w)}")
    return blk


def _fits(got, want, where: str) -> None:
    """``got`` has ``want``'s tree and every leaf its shape."""
    import jax

    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError(f"{where}: the checkpoint gives "
                         f"{jax.tree.structure(got)}, the program's tree is "
                         f"{jax.tree.structure(want)}")
    for (path, g), w in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        if tuple(g.shape) != tuple(w.shape):
            raise ValueError(f"{where}{jax.tree_util.keystr(path)}: the "
                             f"checkpoint gives {tuple(g.shape)}, the "
                             f"program {tuple(w.shape)}")


@functools.cache
def _put():
    import jax

    def put(stack, layer, j):
        return jax.tree.map(lambda a, b: a.at[j].set(b.astype(a.dtype)),
                            stack, layer)
    return jax.jit(put, donate_argnums=0)


def load_params(cfg: dict, model) -> dict:
    """The program's parameters for ``model`` from the published checkpoint
    drawn from ``cfg["weight_seed"]``: the leading dense layers into the
    ``dense_blocks`` stack and the MoE layers into ``blocks``, one layer at
    a time into the stacks in place."""
    import jax
    import jax.numpy as jnp

    s, seed = ref.spec(cfg), cfg["weight_seed"]
    want = jax.eval_shape(lambda: init_params(model, jax.random.key(0)))
    top = ref.weights(s, seed)
    params = {"embed": top["model.embed_tokens.weight"],
              "final_norm": top["model.norm.weight"],
              "lm_head": top["lm_head.weight"].T}
    kinds = [ref.is_moe(s, i) for i in range(s["num_hidden_layers"])]
    if kinds != sorted(kinds):
        raise ValueError("the program stacks every dense layer before the "
                         "MoE layers")
    for name, moe in (("dense_blocks", False), ("blocks", True)):
        layers = [i for i, k in enumerate(kinds) if k == moe]
        if not layers:
            continue
        if name not in want:
            raise ValueError(f"the program's tree has no {name!r} for "
                             f"{len(layers)} layers")
        stack = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                             want[name])
        n = jax.tree.leaves(stack)[0].shape[0]
        if n != len(layers):
            raise ValueError(f"{name}: the program stacks {n} layers, the "
                             f"checkpoint has {len(layers)}")
        for j, i in enumerate(layers):
            layer = _program_layer(ref.weights(s, seed, i), s)
            _fits(layer, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                want[name]), f"layer {i}")
            stack = _put()(stack, layer, j)
        params[name] = stack
    _fits(params, want, "params")
    return jax.tree.map(lambda a, w: a.astype(w.dtype), params, want)


@dataclasses.dataclass
class Setup:
    config: dict
    model: object               # the program's ModelConfig
    engine: object              # the program's ServeEngine
    shape: lm_work.Shape
    peaks: dict


@dataclasses.dataclass
class Log:
    """Per request, in seconds from the window's open: sent (``due``),
    first token, completed; prompt length, tokens asked for, whether its
    logits were kept, and the engine's request."""
    due: np.ndarray
    t_first: np.ndarray
    t_done: np.ndarray
    plen: np.ndarray
    n_new: np.ndarray
    captured: np.ndarray
    requests: list


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    cell: dict
    config: dict
    seconds: float
    setup_s: float
    seed: int
    log: Log
    steps: dict           # per engine step: t_end, n_decoded, n_admitted
    prefills: dict        # the engine's prefill records in the window
    decodes: dict         # its decode records in the window
    counters: dict        # the engine's counters over the window
    expert_counts: np.ndarray | None   # (MoE layers, experts), window
    shape: lm_work.Shape
    peaks: dict
    trace: trace.Summary | None

    def in_window(self, t: np.ndarray) -> np.ndarray:
        return (t >= 0.0) & (t <= self.seconds)


def set_up(cfg: dict, device: dict) -> Setup:
    model = model_config(cfg)
    engine = ServeEngine(model, load_params(cfg, model),
                         max_batch=cfg["max_batch"], max_len=cfg["max_len"])
    # every prefill bucket, the insert, the decode and the captured row
    # served once: nothing compiles inside the window
    for bucket in cfg["prefill_buckets"]:
        plen = bucket // 2 + 1          # a prompt that takes this bucket
        engine.submit([1] * plen, max_new_tokens=2, capture=1)
        while engine.take_finished() == []:
            engine.step()
    gc.collect()
    gc.freeze()
    return Setup(cfg, model, engine, lm_work.shape(cfg),
                 peaks(device["kind"]))


def rows(su: Setup, seed: int) -> np.ndarray:
    """The token ids this seed's prompts are cut from, uniform over the
    vocabulary."""
    return np.random.default_rng([seed, 1]).integers(
        0, su.config["vocab_size"], size=POOL_TOKENS, dtype=np.int32)


class Ctx:
    """What the traffic kind sees (``loadgen/lm_closed.py``): it sends and
    steps; the context keeps the records, captures a seeded share of the
    requests' logits, and marks the window."""

    def __init__(self, su: Setup, pool: np.ndarray, t0: float,
                 seconds: float, seed: int, sess) -> None:
        self.engine, self.pool = su.engine, pool
        self.t0, self.seconds, self.grace_s = t0, seconds, GRACE_S
        self.clock = CLOCK
        self.rng = np.random.default_rng([seed, 2])     # the traffic's
        self._pick = np.random.default_rng([seed, 3])
        self._offsets = np.random.default_rng([seed, 5])
        chk = su.config["check"]
        self._share, self._steps = chk["capture_share"], chk["capture_steps"]
        self._sess = sess
        self._open = self._closed = False
        self.sent: list = []            # [t_sent, request id, capture]
        self.requests: dict = {}
        self.steps: list = []           # [t_end, n_decoded, n_admitted]
        self.in_flight = 0
        self.marks: dict = {}

    def send(self, plen: int, max_new: int) -> None:
        off = int(self._offsets.integers(0, len(self.pool) - plen))
        capture = (self._steps if max_new > self._steps
                   and self._pick.random() < self._share else 0)
        with session.span("bench.submit"):
            rid = self.engine.submit(self.pool[off:off + plen].tolist(),
                                     max_new_tokens=max_new, capture=capture)
        self.sent.append((self.clock(), rid, capture > 0, plen, max_new))
        self.in_flight += 1

    def _edge(self, name: str) -> None:
        eng = self.engine
        self._sess.mark(name)
        self.marks[name] = (self.clock(), dict(eng.counters),
                            None if eng.expert_counts is None
                            else eng.expert_counts.copy())

    def step(self) -> int:
        now = self.clock()
        if not self._open and now >= self.t0:
            self._open = True
            self._edge(trace.OPEN)
        if self._open and not self._closed and now >= self.t0 + self.seconds:
            self._closed = True
            self._edge(trace.CLOSE)
        before = len(self.engine.prefill_records)
        with session.span("bench.step"):
            out = self.engine.step()
        t = self.clock()
        admitted = len(self.engine.prefill_records) - before
        done = self.engine.take_finished()
        for req in done:
            self.requests[req.rid] = (t, req)
        self.steps.append((t, len(out), admitted))
        self.in_flight -= len(done)
        return len(done)

    def finish(self) -> None:
        if not self._open:
            self._open = True
            self._edge(trace.OPEN)
        if not self._closed:
            self._closed = True
            self._edge(trace.CLOSE)


def _records(rows, fields, t0: float, seconds: float) -> dict:
    a = np.asarray(list(rows), np.float64).reshape(-1, len(fields))
    rec = dict(zip(fields, a.T))
    rec["t0"] = rec["t0"] - t0
    keep = (rec["t0"] >= 0.0) & (rec["t0"] <= seconds)
    return {k: v[keep] for k, v in rec.items()}


def measure(su: Setup, cell: dict, traffic: dict, pool: np.ndarray,
            seed: int, seconds: float, traced: bool, t_start: float) -> Run:
    """One window of ``traffic`` on a ready configuration."""
    kind = importlib.import_module(f"loadgen.{traffic['kind']}")
    eng = su.engine
    with session.Session(traced) as sess:
        setup_s = CLOCK() - t_start
        t0 = CLOCK() + max(LEAD_S, float(traffic.get("preroll_s", 0.0)))
        ctx = Ctx(su, pool, t0, seconds, seed, sess)
        kind.drive(ctx, traffic)
        ctx.finish()
    n = len(ctx.sent)
    due = np.array([s[0] for s in ctx.sent]).reshape(n) - t0
    t_first = np.full(n, np.nan)
    t_done = np.full(n, np.nan)
    reqs = []
    for i, (t_sent, rid, _, _, _) in enumerate(ctx.sent):
        t, req = ctx.requests.get(rid, (np.nan, None))
        reqs.append(req)
        t_done[i] = t - t0
        if req is not None and req.ttft_s is not None:
            t_first[i] = due[i] + req.ttft_s
    log = Log(due, t_first, t_done,
              np.array([s[3] for s in ctx.sent], np.int64).reshape(n),
              np.array([s[4] for s in ctx.sent], np.int64).reshape(n),
              np.array([s[2] for s in ctx.sent], bool).reshape(n), reqs)
    (_, c0, e0), (_, c1, e1) = ctx.marks[trace.OPEN], ctx.marks[trace.CLOSE]
    st = np.asarray(ctx.steps, np.float64).reshape(-1, 3)
    return Run(cell, su.config, seconds, setup_s, seed, log,
               {"t_end": st[:, 0] - t0, "n_decoded": st[:, 1],
                "n_admitted": st[:, 2]},
               _records(eng.prefill_records, PREFILL_FIELDS, t0, seconds),
               _records(eng.decode_records, DECODE_FIELDS, t0, seconds),
               {k: c1[k] - c0[k] for k in c1},
               None if e0 is None else e1 - e0,
               su.shape, su.peaks, sess.summary)


def numbers(r: Run, pool: np.ndarray, control: bool = False
            ) -> tuple[dict, int, int]:
    """Compare a seeded sample of the answered requests' logits with the
    reference's: the compared numbers, the requests sent inside the window
    and those of them never answered.  With ``control`` the logits compared
    are the reference's own in float8."""
    cfg, log = r.config, r.log
    chk = cfg["check"]
    steps = chk["capture_steps"]
    sent = r.in_window(log.due)
    answered = np.isfinite(log.t_done)
    failed = int(np.sum(sent & ~answered))
    cand = np.nonzero(answered & log.captured)[0]
    cand = [i for i in cand if len(log.requests[i].logits) == steps + 1]
    nums = {"logit_rms_err": float("inf"), "token_miss_share": float("inf"),
            "unanswered": float(failed)}
    if len(cand) < chk["sample"]:
        return nums, int(np.sum(sent)), failed
    pick = np.random.default_rng([r.seed, 4]).choice(
        cand, size=chk["sample"], replace=False)
    spec, seed = ref.spec(cfg), cfg["weight_seed"]
    worst, miss, total = 0.0, 0, 0
    for i in sorted(pick):
        req = log.requests[i]
        plen = len(req.prompt)
        seq = list(req.prompt) + req.tokens[:steps]
        at = np.arange(plen - 1, plen + steps)
        want = np.asarray(ref.forward(spec, seed, seq, at,
                                      length=cfg["max_len"]), np.float64)
        if control:
            got = np.asarray(ref.forward(
                spec, seed, seq, at, rounding=cfg["control"]["kind"],
                length=cfg["max_len"]), np.float64)
            tok = got.argmax(axis=1)
        else:
            got = np.stack(req.logits).astype(np.float64)[:, :cfg["vocab_size"]]
            tok = np.asarray(req.tokens[:steps + 1])
        err = np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))
        worst = max(worst, float(err) if np.isfinite(err) else float("inf"))
        gap = (want.max(axis=1) - want[np.arange(len(at)), tok]) / want.std(
            axis=1)
        miss += int(np.sum(gap > chk["miss_gap"]))
        total += len(at)
    nums.update(logit_rms_err=worst, token_miss_share=miss / total)
    return nums, int(np.sum(sent)), failed
