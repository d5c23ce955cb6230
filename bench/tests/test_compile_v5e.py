"""Every tenant of every configuration compiles for a described TPU v5e at
the largest bucket, as the served forward (``BatchedProgram.fn``) and not as
a kernel alone, with its kernel in the compiled program.  No chip needed.

Run with an explicit path: ``python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
TENANTS = [(c, i) for c in CONFIGS for i in range(len(json.loads(
    (BENCH / "configs" / f"{c}.json").read_text())["tenants"]))]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("config,index", TENANTS)
def test_tenant_forward_compiles_for_v5e(config, index, one_chip,
                                         monkeypatch):
    import jax

    from harness import fleet
    from repro.core.compiler import MafiaCompiler
    from repro.kernels import megakernel

    # the backend here is the CPU, which would pick Pallas interpret mode
    monkeypatch.setattr(megakernel, "resolve_interpret", lambda i: False)
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    fam = fleet.family(cfg)
    t = cfg["tenants"][index]
    params = fam.init(t, [cfg["weight_seed"], index])
    from loadgen import features

    dep = features.deployment(t["n_features"], t["n_classes"],
                              [cfg["weight_seed"], index, 1])
    prog = MafiaCompiler(
        use_pallas=True, precision=cfg["precision"],
        exec_mode=cfg["exec_mode"]).compile(
            fam.build_dfg(params, t),
            calib=None if cfg["precision"] == "float32" else dep.calib)
    batched = prog.batch(cfg["max_batch"])
    (name,) = prog.dfg.graph_inputs
    arg = {name: jax.ShapeDtypeStruct((cfg["max_batch"], t["n_features"]),
                                      np.float32, sharding=one_chip)}
    text = batched.fn.lower(arg).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
