"""Public entry point + tunable declaration for the conv2d case study."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import SearchSpace, Tuner, TuningCache
from ...core.profiles import DeviceProfile, TPU_V5E
from ...core.registry import AutotunePolicy, Shape, lookup, tunable
from ...core.space import Config
from .conv2d import (analytical_time, make_conv2d,
                     vmem_footprint)
from .ref import conv2d_reference

KERNEL_NAME = "conv2d"


def _shape(H: int, W: int, Fh: int, Fw: int) -> Dict[str, Any]:
    return {"H": H, "W": W, "Fh": Fh, "Fw": Fw}


def shape_key(H: int, W: int, Fh: int, Fw: int) -> str:
    return f"H{H}_W{W}_F{Fh}x{Fw}"


def heuristic_config(H: int, W: int, Fh: int, Fw: int) -> Dict[str, Any]:
    # tiny images make min(...) fall outside the declared value lists;
    # the registry's project_feasible snaps those to the nearest in-space
    # values before the config is served
    return {"BLOCK_H": min(16, H), "BLOCK_W": min(256, W),
            "SUB_H": 1, "UNROLL": True, "HALO_MODE": "materialize"}


def tuning_space(extended: bool = False):
    """Conv parameter space (compare paper Table II: 3424 configurations)."""
    if extended:
        params = {
            "BLOCK_H": (4, 8, 16, 32, 64, 128),
            "BLOCK_W": (64, 128, 256, 512, 1024),
            "SUB_H": (1, 2, 4, 8),
            "UNROLL": (True, False),
            "HALO_MODE": ("materialize", "xla"),
            "PAD_W": (0, 1),
            "PIPELINE_DEPTH": (2, 3, 4),
        }
    else:
        params = {
            "BLOCK_H": (8, 16, 32),
            "BLOCK_W": (128, 256),
            "SUB_H": (1, 2),
            "UNROLL": (True, False),
            "HALO_MODE": ("materialize", "xla"),
        }
    constraints = [
        (lambda bh, s: bh % s == 0, ("BLOCK_H", "SUB_H"),
         "BLOCK_H divisible by SUB_H"),
    ]
    return params, constraints


def _space(shape: Shape, extended: bool = True) -> SearchSpace:
    params, constraints = tuning_space(extended=extended)
    sp = SearchSpace()
    for name, values in params.items():
        sp.add_parameter(name=name, values=values)
    for fn, names, label in constraints:
        sp.add_constraint(fn, names, label)
    return sp


def _make_args(shape: Shape, rng: np.random.Generator):
    H, W, Fh, Fw = shape["H"], shape["W"], shape["Fh"], shape["Fw"]
    img = jnp.asarray(rng.normal(size=(H, W)), jnp.float32)
    flt = jnp.asarray(rng.normal(size=(Fh, Fw)), jnp.float32)
    return img, flt


def _arg_specs(shape: Shape):
    H, W, Fh, Fw = shape["H"], shape["W"], shape["Fh"], shape["Fw"]
    return (jax.ShapeDtypeStruct((H, W), jnp.float32),
            jax.ShapeDtypeStruct((Fh, Fw), jnp.float32))


def _elt_bytes(shape: Shape) -> int:
    """Image element width from the shape's dtype (default float32)."""
    return jnp.dtype(shape.get("dtype", "float32")).itemsize


@tunable(
    name=KERNEL_NAME,
    space=_space,
    heuristic=lambda s: heuristic_config(s["H"], s["W"], s["Fh"], s["Fw"]),
    shape_key=lambda s: shape_key(s["H"], s["W"], s["Fh"], s["Fw"]),
    make_args=_make_args,
    arg_specs=_arg_specs,
    # dtype threads through model and footprint with the same element
    # width so static VMEM proofs agree with the analytical cliff
    analytical_model=lambda s, cfg, prof: analytical_time(
        cfg, prof, s["H"], s["W"], s["Fh"], s["Fw"],
        elt_bytes=_elt_bytes(s)),
    vmem_footprint=lambda s, cfg: vmem_footprint(
        cfg, s["Fh"], s["Fw"], elt_bytes=_elt_bytes(s)),
    reference=lambda s: conv2d_reference,
    default_shapes=(_shape(4096, 4096, 3, 3),),
    # paper V-B: budget 107 = 1/32 of the 3424-config EXTENDED space, so
    # registry-driven tuning must search that space too
    defaults={"strategy": "annealing", "budget": 107, "extended_space": True},
    tags=("paper-case-study", "conv"))
def CONV2D(shape: Shape, config: Config, *, interpret: bool = False):
    """The paper's section V case study: 2D convolution."""
    return make_conv2d(shape["H"], shape["W"], shape["Fh"], shape["Fw"],
                       config, interpret=interpret)


def lookup_config(H: int, W: int, Fh: int, Fw: int,
                  profile: DeviceProfile = TPU_V5E,
                  cache: Optional[TuningCache] = None,
                  policy: "AutotunePolicy | str | None" = None
                  ) -> Dict[str, Any]:
    return lookup(CONV2D, _shape(H, W, Fh, Fw), profile=profile, cache=cache,
                  policy=policy)


def conv2d(image: jax.Array, filt: jax.Array,
           config: Optional[Dict[str, Any]] = None, weight: float = 1.0,
           profile: DeviceProfile = TPU_V5E, interpret: bool = False,
           policy: "AutotunePolicy | str | None" = None):
    H, W = image.shape
    Fh, Fw = filt.shape
    cfg = config or lookup_config(H, W, Fh, Fw, profile, policy=policy)
    return make_conv2d(H, W, Fh, Fw, cfg, weight=weight,
                       interpret=interpret)(image, filt)


# ---------------------------------------------------------------------------
# legacy tuner integration — thin delegates to the generic API
# ---------------------------------------------------------------------------

def make_tuner(H: int, W: int, Fh: int, Fw: int, *, evaluator=None,
               profile: DeviceProfile = TPU_V5E,
               interpret: Optional[bool] = None,
               extended_space: bool = True) -> Tuner:
    return Tuner.from_tunable(CONV2D, _shape(H, W, Fh, Fw),
                              evaluator=evaluator, profile=profile,
                              interpret=interpret,
                              extended_space=extended_space)


def tune_conv2d(H: int, W: int, Fh: int, Fw: int,
                strategy: str = "annealing", budget: int = 107,
                profile: DeviceProfile = TPU_V5E, record: bool = True,
                seed: int = 0, **kwargs):
    """Paper section V-B used budget=107 (1/32 of its 3424-config space)."""
    from ...tune.api import tune_kernel
    kwargs.setdefault("extended_space", True)
    return tune_kernel(CONV2D, _shape(H, W, Fh, Fw), strategy=strategy,
                       budget=budget, profile=profile, record=record,
                       seed=seed, **kwargs)
