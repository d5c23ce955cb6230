"""Architecture registry + shape cells.

Every assigned architecture registers an :class:`ArchSpec`: the exact
full-size :class:`~repro.models.transformer.ModelConfig` from the public
config, a *reduced* smoke config of the same family (exercised on CPU in
tests), and per-shape-cell metadata (microbatching, long-context window,
documented skips).

Shape cells (fixed by the assignment):

    train_4k      seq 4,096   × global batch 256   → lowers ``train_step``
    prefill_32k   seq 32,768  × global batch 32    → lowers ``prefill_step``
    decode_32k    seq 32,768  × global batch 128   → lowers ``serve_step``
                  (1 new token against a 32k KV cache)
    long_500k     seq 524,288 × global batch 1     → ``serve_step``; needs
                  sub-quadratic attention → run only for ssm/hybrid archs,
                  skip (with reason) for pure full-attention archs.
"""

from __future__ import annotations

import dataclasses
import importlib

from repro.models.transformer import ModelConfig

__all__ = ["ArchSpec", "ShapeCell", "SHAPES", "ARCH_IDS", "get_arch",
           "all_archs", "cells_for"]


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str               # train | prefill | decode
    seq_len: int
    global_batch: int
    long_context: bool = False


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524_288, 1, long_context=True),
}


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    arch_id: str
    source: str                      # public provenance ([arXiv/hf; tier])
    model: ModelConfig
    smoke: ModelConfig
    train_microbatches: int = 8      # gradient-accumulation steps for train_4k
    long_ctx_window: int = 4096      # sliding window used at long_500k (hybrid)
    skip_cells: dict[str, str] = dataclasses.field(default_factory=dict)

    def cell_config(self, cell: ShapeCell) -> ModelConfig:
        """ModelConfig specialized for one shape cell."""
        cfg = self.model
        if cell.long_context and cfg.family == "hybrid":
            cfg = dataclasses.replace(cfg, attn_window=self.long_ctx_window)
        if cell.kind != "train":
            # inference: bf16 weights, no remat (fp32 masters are train-only)
            cfg = dataclasses.replace(cfg, remat=False, param_dtype="bfloat16")
        elif cell.seq_len <= 4096:
            # flash kv-chunking exists to bound long-sequence score memory;
            # at ≤4k the chunk loop is pure overhead (stacked per-chunk masks
            # + carried fp32 stats — measured 11% of the memory term,
            # EXPERIMENTS.md §Perf) — run attention single-chunk.
            cfg = dataclasses.replace(cfg, kv_chunk=max(cfg.kv_chunk,
                                                        cell.seq_len))
        return cfg


_FULL_ATTN_SKIP = (
    "long_500k needs sub-quadratic attention history; this arch is pure "
    "full-attention (O(S) KV history per layer) — skipped per the shape "
    "rule, recorded in DESIGN.md §Arch-applicability"
)

ARCH_IDS: list[str] = [
    "olmoe-1b-7b",
    "deepseek-v2-236b",
    "musicgen-medium",
    "internvl2-26b",
    "granite-8b",
    "command-r-35b",
    "codeqwen1.5-7b",
    "qwen2.5-3b",
    "zamba2-7b",
    "mamba2-1.3b",
]

# served configurations beside the assigned ones: resolvable by name, not
# part of the dry-run's shape cells
SERVED_IDS: list[str] = ["deepseek-v2-lite"]

_MODULES = {a: "repro.configs." + a.replace("-", "_").replace(".", "_")
            for a in ARCH_IDS + SERVED_IDS}

_CACHE: dict[str, ArchSpec] = {}


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in _CACHE:
        if arch_id not in _MODULES:
            raise KeyError(f"unknown arch {arch_id!r}; known: "
                           f"{ARCH_IDS + SERVED_IDS}")
        mod = importlib.import_module(_MODULES[arch_id])
        spec = mod.SPEC
        assert spec.arch_id == arch_id
        _CACHE[arch_id] = spec
    return _CACHE[arch_id]


def all_archs() -> list[ArchSpec]:
    return [get_arch(a) for a in ARCH_IDS]


def cells_for(spec: ArchSpec) -> list[ShapeCell]:
    """The runnable shape cells for an arch (skips excluded)."""
    return [c for n, c in SHAPES.items() if n not in spec.skip_cells]


def default_skips(family: str) -> dict[str, str]:
    if family in ("ssm", "hybrid"):
        return {}
    return {"long_500k": _FULL_ATTN_SKIP}
