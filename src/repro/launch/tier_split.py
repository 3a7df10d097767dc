"""Tier-split deployment: S-tier and L-tier on SEPARATE pods.

The single-program HI case (`make_hi_decode_case`) runs both tiers over one
mesh — the right plan when ED and ES share a fabric.  The paper's actual
topology is two *systems* joined by a narrow link; on a 2-pod machine that
maps to: S-tier owns pod 0's 256 chips, L-tier owns pod 1's, and the only
inter-pod traffic is the escalated token batch + returned logits (the DCN
offload link, measured below as beta_bytes).

Implemented as two separately-lowered programs on disjoint sub-meshes (the
realistic serving architecture — the ES is its own binary); the host-side
router glues them, exactly like the HIEngine does on CPU.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import router as router_mod
from repro.launch import specs as case_specs
from repro.models import model_zoo
from repro.sharding import specs as sh


def make_tier_meshes(shape: Optional[Tuple[int, int]] = None
                     ) -> Tuple[Mesh, Mesh]:
    """Two disjoint (data, model) meshes of ``shape`` each, split from the
    front of ``jax.devices()`` — S tier first, L tier second.

    ``shape=None`` keeps the historical default: two 16x16 pods from the
    512-device dry-run env.  Any smaller shape (e.g. ``(2, 2)`` on an
    8-forced-device CPU host) splits whatever devices exist, so the split is
    exercisable in plain-CPU tests without the dry-run harness.
    """
    shape = (16, 16) if shape is None else tuple(shape)
    per = shape[0] * shape[1]
    devs = jax.devices()
    if len(devs) < 2 * per:
        raise RuntimeError(
            f"tier split needs {2 * per} devices for two {shape} meshes, "
            f"have {len(devs)} (the 512-device dry-run env provides the "
            f"default 2x(16,16))")
    s_devs = np.asarray(devs[:per]).reshape(shape)
    l_devs = np.asarray(devs[per:2 * per]).reshape(shape)
    return (Mesh(s_devs, ("data", "model")), Mesh(l_devs, ("data", "model")))


@dataclass
class TierSplitReport:
    s_compile: Dict[str, Any]
    l_compile: Dict[str, Any]
    beta_bytes_per_step: int          # escalation link traffic


def lower_tier_split(cfg: ModelConfig, shape: ShapeConfig, *,
                     capacity_factor: float = 0.5, s_scale: int = 4
                     ) -> TierSplitReport:
    """Lower + compile serve_step for each tier on its own pod; report the
    inter-pod escalation bytes (the paper's beta, now a DCN transfer)."""
    mesh_s, mesh_l = make_tier_meshes()
    s_cfg = cfg.s_variant(s_scale)
    b = shape.global_batch
    cap = router_mod.capacity_for(b, capacity_factor)
    cap = max(16, (cap // 16) * 16)      # keep shardable on the L mesh

    reports = {}
    for name, mesh, mcfg, batch in (("s", mesh_s, s_cfg, b),
                                    ("l", mesh_l, cfg, cap)):
        params = model_zoo.init_params_spec(mcfg)
        cache = model_zoo.cache_spec(mcfg, batch, shape.seq_len)
        token = jax.ShapeDtypeStruct((batch, 1), "int32")
        fsdp = case_specs.serving_fsdp(mcfg, mesh)
        p_sh = sh.param_shardings(params, mesh, fsdp=fsdp)
        c_specs = sh.cache_specs(mcfg, mesh,
                                 dataclasses.replace(shape,
                                                     global_batch=batch))
        c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs)
        t_sh = NamedSharding(mesh, P("data" if batch % 16 == 0 else None,
                                     None))

        def step(params, token, cache, _mcfg=mcfg):
            return model_zoo.decode_step(params, _mcfg, token, cache)

        with jax.set_mesh(mesh):
            compiled = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh),
                               out_shardings=(t_sh, c_sh),
                               donate_argnums=(2,)).lower(
                params, token, cache).compile()
        mem = compiled.memory_analysis()
        reports[name] = {
            "chips": mesh.size,
            "peak_gb_per_device":
                (getattr(mem, "temp_size_in_bytes", 0)
                 + getattr(mem, "argument_size_in_bytes", 0)
                 + getattr(mem, "output_size_in_bytes", 0)) / 1e9,
        }

    # the DCN link: escalated tokens out (cap x 1 int32) + logits back
    # (cap x vocab fp32) per step
    beta = cap * 4 + cap * cfg.vocab_size * 4
    return TierSplitReport(reports["s"], reports["l"], beta)
