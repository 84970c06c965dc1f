"""Batched/multi-head wrapper + tunable declaration for flash attention."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import SearchSpace, Tuner, TuningCache
from ...core.profiles import DeviceProfile, TPU_V5E
from ...core.registry import AutotunePolicy, Shape, lookup, tunable
from ...core.space import Config
from .flash import (analytical_time, make_flash_attention,
                    vmem_footprint)
from .ref import attention_reference

KERNEL_NAME = "flash_attention"


def _shape(Sq: int, Sk: int, D: int, causal: bool = True) -> Dict[str, Any]:
    return {"Sq": Sq, "Sk": Sk, "D": D, "causal": bool(causal)}


def shape_key(Sq: int, Sk: int, D: int, causal: bool = True) -> str:
    return f"Sq{Sq}_Sk{Sk}_D{D}_{'c' if causal else 'f'}"


def heuristic_config(Sq: int, Sk: int) -> Dict[str, Any]:
    def pick(d, cands):
        for c in cands:
            if d % c == 0:
                return c
        # no candidate divides d: return d itself — likely out of the
        # declared value list, which the registry's feasibility projection
        # (project_feasible) repairs to the nearest in-space point
        return d
    # PIPELINE_DEPTH is declared explicitly: a heuristic must cover every
    # space parameter or the constraint check reads it as a violation
    return {"BLOCK_Q": pick(Sq, (512, 256, 128, 64)),
            "BLOCK_K": pick(Sk, (1024, 512, 256, 128, 64)),
            "PIPELINE_DEPTH": 2}


def tuning_space():
    params = {
        "BLOCK_Q": (64, 128, 256, 512, 1024),
        "BLOCK_K": (64, 128, 256, 512, 1024, 2048),
        "PIPELINE_DEPTH": (2, 3),
    }
    return params, []


def _space(shape: Shape) -> SearchSpace:
    Sq, Sk = shape["Sq"], shape["Sk"]
    params, constraints = tuning_space()
    sp = SearchSpace()
    for name, values in params.items():
        sp.add_parameter(name=name, values=values)
    for fn, names, label in constraints:
        sp.add_constraint(fn, names, label)
    sp.add_constraint(lambda bq: Sq % bq == 0, ("BLOCK_Q",), "Sq % BLOCK_Q")
    sp.add_constraint(lambda bk: Sk % bk == 0, ("BLOCK_K",), "Sk % BLOCK_K")
    return sp


def _make_args(shape: Shape, rng: np.random.Generator):
    Sq, Sk, D = shape["Sq"], shape["Sk"], shape["D"]
    mk = lambda s: jnp.asarray(rng.normal(size=s) * 0.5, jnp.float32)
    return mk((Sq, D)), mk((Sk, D)), mk((Sk, D))


def _arg_specs(shape: Shape):
    Sq, Sk, D = shape["Sq"], shape["Sk"], shape["D"]
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((Sq, D), f32),
            jax.ShapeDtypeStruct((Sk, D), f32),
            jax.ShapeDtypeStruct((Sk, D), f32))


def _elt_bytes(shape: Shape) -> int:
    """Activation element width from the shape's dtype (default float32)."""
    return jnp.dtype(shape.get("dtype", "float32")).itemsize


@tunable(
    name=KERNEL_NAME,
    space=_space,
    heuristic=lambda s: heuristic_config(s["Sq"], s["Sk"]),
    shape_key=lambda s: shape_key(s["Sq"], s["Sk"], s["D"],
                                  s.get("causal", True)),
    make_args=_make_args,
    arg_specs=_arg_specs,
    # dtype threads through model and footprint with the same element
    # width so static VMEM proofs agree with the analytical cliff
    analytical_model=lambda s, cfg, prof: analytical_time(
        cfg, prof, s["Sq"], s["Sk"], s["D"],
        causal=s.get("causal", True), elt_bytes=_elt_bytes(s)),
    vmem_footprint=lambda s, cfg: vmem_footprint(
        cfg, s["D"], elt_bytes=_elt_bytes(s)),
    reference=lambda s: (lambda q, k, v: attention_reference(
        q, k, v, causal=s.get("causal", True))),
    default_shapes=(_shape(4096, 4096, 128, causal=True),),
    defaults={"strategy": "annealing", "budget": 40},
    tags=("beyond-paper", "attention"))
def FLASH_ATTENTION(shape: Shape, config: Config, *, interpret: bool = False):
    """Flash attention (beyond paper; same tuning methodology)."""
    return make_flash_attention(shape["Sq"], shape["Sk"], shape["D"], config,
                                causal=shape.get("causal", True),
                                interpret=interpret)


def lookup_config(Sq: int, Sk: int, D: int, causal: bool = True,
                  profile: DeviceProfile = TPU_V5E,
                  cache: Optional[TuningCache] = None,
                  policy: "AutotunePolicy | str | None" = None
                  ) -> Dict[str, Any]:
    return lookup(FLASH_ATTENTION, _shape(Sq, Sk, D, causal),
                  profile=profile, cache=cache, policy=policy)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    config: Optional[Dict[str, Any]] = None,
                    profile: DeviceProfile = TPU_V5E,
                    interpret: bool = False,
                    policy: "AutotunePolicy | str | None" = None):
    """q: (..., Sq, D), k/v: (..., Sk, D); leading dims vmapped."""
    *lead, Sq, D = q.shape
    Sk = k.shape[-2]
    cfg = config or lookup_config(Sq, Sk, D, causal, profile, policy=policy)
    fn = make_flash_attention(Sq, Sk, D, cfg, causal=causal,
                              dtype=q.dtype, interpret=interpret)
    for _ in lead:
        fn = jax.vmap(fn)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# legacy tuner integration — thin delegates to the generic API
# ---------------------------------------------------------------------------

def make_tuner(Sq: int, Sk: int, D: int, *, causal: bool = True,
               evaluator=None, profile: DeviceProfile = TPU_V5E,
               interpret: Optional[bool] = None) -> Tuner:
    return Tuner.from_tunable(FLASH_ATTENTION, _shape(Sq, Sk, D, causal),
                              evaluator=evaluator, profile=profile,
                              interpret=interpret)


def tune_flash_attention(Sq: int, Sk: int, D: int, *, causal: bool = True,
                         strategy: str = "annealing", budget: int = 40,
                         profile: DeviceProfile = TPU_V5E,
                         record: bool = True, seed: int = 0, **kwargs):
    from ...tune.api import tune_kernel
    return tune_kernel(FLASH_ATTENTION, _shape(Sq, Sk, D, causal),
                       strategy=strategy, budget=budget, profile=profile,
                       record=record, seed=seed, **kwargs)
