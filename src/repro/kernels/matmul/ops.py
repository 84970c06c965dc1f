"""Public entry point for the tuned GEMM, declared via the tunable registry.

``GEMM`` is the complete tuning declaration (space, heuristic, models,
reference) for the shape family; ``matmul(a, b)`` resolves its block
configuration through ``repro.core.registry.lookup`` — tuned-cache hit,
then heuristic, with optional tune-on-miss (CLTune scenario 3).  The old
per-kernel helpers (``make_tuner``/``tune_matmul``/``lookup_config``)
survive as thin delegates to the generic API.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import SearchSpace, Tuner, TuningCache
from ...core.profiles import DeviceProfile, TPU_V5E
from ...core.registry import AutotunePolicy, Shape, lookup, tunable
from ...core.space import Config
from . import ref
from .matmul import (analytical_time, make_matmul,
                     vmem_footprint)

KERNEL_NAME = "gemm"


def _shape(M: int, N: int, K: int, dtype="float32") -> Dict[str, Any]:
    return {"M": M, "N": N, "K": K, "dtype": jnp.dtype(dtype).name}


def shape_key(M: int, N: int, K: int, dtype="float32") -> str:
    return f"M{M}_N{N}_K{K}_{jnp.dtype(dtype).name}"


def heuristic_config(M: int, N: int, K: int) -> Dict[str, Any]:
    """Largest aligned blocks that divide the problem; sensible defaults."""
    def pick(d, cands):
        for c in cands:
            if d % c == 0:
                return c
        # nothing divides d (odd/prime dims): return d itself — the
        # registry's project_feasible repairs out-of-list values to the
        # nearest in-space point before the config is ever served
        return d
    return {
        "BLOCK_M": pick(M, (512, 256, 128, 64, 32, 16, 8)),
        "BLOCK_N": pick(N, (512, 256, 128, 64, 32, 16, 8)),
        "BLOCK_K": pick(K, (512, 256, 128, 64, 32, 16, 8)),
        "GRID_ORDER": "mn", "INNER_STEPS": 1,
        "ACC_DTYPE": "float32", "ACC_IN_OUTPUT": False, "TRANS_A": False,
    }


def tuning_space(extended: bool = False):
    """(values, constraints) for the GEMM space.

    ``extended=True`` is the paper-scale space (>200k configurations,
    benchmark Fig. 7); the compact space is what tests sweep with real
    Pallas-interpret execution.
    """
    if extended:
        params = {
            "BLOCK_M": (32, 64, 128, 256, 512, 1024),
            "BLOCK_N": (32, 64, 128, 256, 512, 1024),
            "BLOCK_K": (32, 64, 128, 256, 512, 1024),
            "GRID_ORDER": ("mn", "nm"),
            "INNER_STEPS": (1, 2, 4, 8),
            "ACC_DTYPE": ("float32", "bfloat16"),
            "ACC_IN_OUTPUT": (False, True),
            "TRANS_A": (False, True),
            "PIPELINE_DEPTH": (2, 3, 4),
            "NBUF_OUT": (1, 2),
            "PACK": (1, 2, 4),
        }
    else:
        params = {
            "BLOCK_M": (128, 256, 512),
            "BLOCK_N": (128, 256, 512),
            "BLOCK_K": (128, 256, 512),
            "GRID_ORDER": ("mn", "nm"),
            "INNER_STEPS": (1, 2),
            "ACC_DTYPE": ("float32",),
            "ACC_IN_OUTPUT": (False, True),
            "TRANS_A": (False,),
        }
    constraints = [
        (lambda bk, s: bk % s == 0, ("BLOCK_K", "INNER_STEPS"),
         "BLOCK_K divisible by INNER_STEPS"),
        (lambda acc_out, acc: (not acc_out) or acc == "float32",
         ("ACC_IN_OUTPUT", "ACC_DTYPE"), "in-place acc requires f32"),
    ]
    return params, constraints


def _space(shape: Shape, extended: bool = False) -> SearchSpace:
    M, N, K = shape["M"], shape["N"], shape["K"]
    params, constraints = tuning_space(extended=extended)
    sp = SearchSpace()
    for name, values in params.items():
        sp.add_parameter(name=name, values=values)
    for fn, names, label in constraints:
        sp.add_constraint(fn, names, label)
    # problem-size divisibility (device-independent feasibility)
    sp.add_constraint(lambda bm: M % bm == 0, ("BLOCK_M",), "M % BLOCK_M")
    sp.add_constraint(lambda bn: N % bn == 0, ("BLOCK_N",), "N % BLOCK_N")
    sp.add_constraint(lambda bk: K % bk == 0, ("BLOCK_K",), "K % BLOCK_K")
    return sp


def _make_args(shape: Shape, rng: np.random.Generator):
    M, N, K = shape["M"], shape["N"], shape["K"]
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    return a, b


def _arg_specs(shape: Shape):
    M, N, K = shape["M"], shape["N"], shape["K"]
    return (jax.ShapeDtypeStruct((M, K), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32))


def _elt_bytes(shape: Shape) -> int:
    """Input element width from the shape's dtype (default float32)."""
    return jnp.dtype(shape.get("dtype", "float32")).itemsize


@tunable(
    name=KERNEL_NAME,
    space=_space,
    heuristic=lambda s: heuristic_config(s["M"], s["N"], s["K"]),
    shape_key=lambda s: shape_key(s["M"], s["N"], s["K"],
                                  s.get("dtype", "float32")),
    make_args=_make_args,
    arg_specs=_arg_specs,
    # dtype threads through the model AND the footprint with the same
    # element width, so a static VMEM proof (repro.analyze) can never
    # disagree with the analytical cliff — pruning stays winner-identical
    analytical_model=lambda s, cfg, prof: analytical_time(
        cfg, prof, s["M"], s["N"], s["K"], elt_bytes=_elt_bytes(s)),
    vmem_footprint=lambda s, cfg: vmem_footprint(
        cfg, elt_bytes=_elt_bytes(s)),
    reference=lambda s: (lambda a, b: ref.gemm_reference(a, b)),
    default_shapes=(_shape(2048, 2048, 2048),),
    defaults={"strategy": "annealing", "budget": 100},
    tags=("paper-case-study", "gemm"))
def GEMM(shape: Shape, config: Config, *, interpret: bool = False):
    """The paper's section VI case study: Pallas-tiled GEMM."""
    return make_matmul(shape["M"], shape["N"], shape["K"], config,
                       interpret=interpret)


def lookup_config(M: int, N: int, K: int,
                  profile: DeviceProfile = TPU_V5E,
                  cache: Optional[TuningCache] = None,
                  policy: "AutotunePolicy | str | None" = None
                  ) -> Dict[str, Any]:
    return lookup(GEMM, _shape(M, N, K), profile=profile, cache=cache,
                  policy=policy)


def matmul(a: jax.Array, b: jax.Array, config: Optional[Dict[str, Any]] = None,
           *, alpha: float = 1.0, beta: float = 0.0,
           c: Optional[jax.Array] = None,
           profile: DeviceProfile = TPU_V5E, interpret: bool = False,
           policy: "AutotunePolicy | str | None" = None):
    """C = alpha * op(A) @ B (+ beta * C), Pallas-tiled.

    The alpha/beta epilogue runs in XLA (it fuses); the Pallas kernel does
    the FLOP-heavy product, as in the paper's GEMM.
    """
    trans = bool((config or {}).get("TRANS_A", False))
    M = a.shape[1] if trans else a.shape[0]
    K = a.shape[0] if trans else a.shape[1]
    N = b.shape[1]
    cfg = config or lookup_config(M, N, K, profile, policy=policy)
    fn = make_matmul(M, N, K, cfg, out_dtype=a.dtype, interpret=interpret)
    out = fn(a, b)
    if alpha != 1.0:
        out = alpha * out
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out


# ---------------------------------------------------------------------------
# legacy tuner integration — thin delegates to the generic API
# ---------------------------------------------------------------------------

def make_tuner(M: int, N: int, K: int, *, evaluator=None,
               profile: DeviceProfile = TPU_V5E,
               interpret: Optional[bool] = None,
               extended_space: bool = False, seed: int = 0) -> Tuner:
    """A ready-to-run Tuner for this GEMM shape (the paper's case study 2)."""
    return Tuner.from_tunable(GEMM, _shape(M, N, K), evaluator=evaluator,
                              profile=profile, interpret=interpret,
                              extended_space=extended_space)


def tune_matmul(M: int, N: int, K: int, strategy: str = "annealing",
                budget: int = 100, profile: DeviceProfile = TPU_V5E,
                record: bool = True, seed: int = 0, **kwargs):
    from ...tune.api import tune_kernel
    return tune_kernel(GEMM, _shape(M, N, K), strategy=strategy,
                       budget=budget, profile=profile, record=record,
                       seed=seed, **kwargs)
