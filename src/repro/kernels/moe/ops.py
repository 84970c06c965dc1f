"""Public op + tunable declaration for the grouped SwiGLU experts.

``MOE_EXPERTS`` declares the tuning problem for one MoE layer's share of
experts; ``moe_experts(...)`` resolves its tile configuration through
``repro.core.registry.lookup`` (tuned-cache hit, then heuristic) and runs
the routed pairs through the grouped kernels.

Shape dict: ``N`` tokens of width ``d``, experts of hidden width ``m``,
``E`` experts routed over, ``E_held`` of them held here, ``k`` experts per
token, and the dtype.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import SearchSpace, TuningCache
from ...core.profiles import DeviceProfile, TPU_V5E
from ...core.registry import AutotunePolicy, Shape, lookup, tunable
from ...core.space import Config
from .grouped import analytical_time, make_moe_experts, vmem_footprint
from .ref import moe_experts_reference

KERNEL_NAME = "moe_experts"

_DIMS = ("N", "d", "m", "E", "E_held", "k")


def _shape(N: int, d: int, m: int, E: int, E_held: int, k: int,
           dtype="float32") -> Dict[str, Any]:
    return {"N": N, "d": d, "m": m, "E": E, "E_held": E_held, "k": k,
            "dtype": jnp.dtype(dtype).name}


def _dims(shape: Shape):
    return tuple(int(shape[name]) for name in _DIMS)


def shape_key(N: int, d: int, m: int, E: int, E_held: int, k: int,
              dtype="float32") -> str:
    return (f"N{N}_d{d}_m{m}_E{E}_h{E_held}_k{k}_"
            f"{jnp.dtype(dtype).name}")


def heuristic_config(N: int, d: int, m: int, E: int, E_held: int,
                     k: int) -> Dict[str, Any]:
    """Row tiles of about a quarter of the balanced group (so padding costs
    about an eighth), and the largest aligned column and K blocks that
    divide both widths."""
    rows = N * k / E
    bm = next((c for c in (512, 256) if c <= rows / 4), 128)
    width = int(np.gcd(d, m))
    # nothing divides an odd width: the width itself, which the registry's
    # feasibility projection repairs before the config is served
    block = next((c for c in (512, 256, 128) if width % c == 0), width)
    return {"BLOCK_M": bm, "BLOCK_N": block, "BLOCK_K": block}


def tuning_space():
    params = {
        "BLOCK_M": (128, 256, 512),
        "BLOCK_N": (128, 256, 512, 1024),
        "BLOCK_K": (128, 256, 512, 1024),
    }
    return params, []


def _space(shape: Shape) -> SearchSpace:
    d, m = shape["d"], shape["m"]
    params, constraints = tuning_space()
    sp = SearchSpace()
    for name, values in params.items():
        sp.add_parameter(name=name, values=values)
    for fn, names, label in constraints:
        sp.add_constraint(fn, names, label)
    sp.add_constraint(lambda bn: d % bn == 0 and m % bn == 0, ("BLOCK_N",),
                      "d % BLOCK_N and m % BLOCK_N")
    sp.add_constraint(lambda bk: d % bk == 0 and m % bk == 0, ("BLOCK_K",),
                      "d % BLOCK_K and m % BLOCK_K")
    return sp


@functools.partial(jax.jit, static_argnums=0)
def _random_args(dims, key):
    """Inputs drawn on the device: x ~ N(0, 1), each token's k distinct
    experts uniform over all E with weights summing to one, and the held
    experts' weights ~ N(0, 1 / fan-in)."""
    N, d, m, E, E_held, k = dims
    kx, kr, kg, ki, ko = jax.random.split(key, 5)
    x = jax.random.normal(kx, (N, d), jnp.float32)
    scores, ids = jax.lax.top_k(jax.random.uniform(kr, (N, E)), k)
    weights = scores / jnp.sum(scores, axis=-1, keepdims=True)

    def expert(key, fan_in, fan_out):
        return jax.random.normal(key, (E_held, fan_in, fan_out),
                                 jnp.float32) / np.sqrt(fan_in)

    return (x, ids.astype(jnp.int32), weights, expert(kg, d, m),
            expert(ki, d, m), expert(ko, m, d))


def _make_args(shape: Shape, rng: np.random.Generator):
    return _random_args(_dims(shape),
                        jax.random.key(int(rng.integers(2**31))))


def _arg_specs(shape: Shape):
    N, d, m, E, E_held, k = _dims(shape)
    f32, i32 = jnp.float32, jnp.int32
    return (jax.ShapeDtypeStruct((N, d), f32),
            jax.ShapeDtypeStruct((N, k), i32),
            jax.ShapeDtypeStruct((N, k), f32),
            jax.ShapeDtypeStruct((E_held, d, m), f32),
            jax.ShapeDtypeStruct((E_held, d, m), f32),
            jax.ShapeDtypeStruct((E_held, m, d), f32))


def _elt_bytes(shape: Shape) -> int:
    """Activation and weight element width from the shape's dtype."""
    return jnp.dtype(shape.get("dtype", "float32")).itemsize


@tunable(
    name=KERNEL_NAME,
    space=_space,
    heuristic=lambda s: heuristic_config(*_dims(s)),
    shape_key=lambda s: shape_key(*_dims(s), s.get("dtype", "float32")),
    make_args=_make_args,
    arg_specs=_arg_specs,
    # dtype threads through model and footprint with the same element
    # width so static VMEM proofs agree with the analytical cliff
    analytical_model=lambda s, cfg, prof: analytical_time(
        cfg, prof, *_dims(s), elt_bytes=_elt_bytes(s)),
    vmem_footprint=lambda s, cfg: vmem_footprint(
        cfg, elt_bytes=_elt_bytes(s)),
    reference=lambda s: moe_experts_reference,
    # DeepSeek-V3's MoE layer as one chip of 32 holds it (8 of 256 experts)
    default_shapes=(_shape(32768, 7168, 2048, 256, 8, 8),),
    defaults={"strategy": "annealing", "budget": 24},
    tags=("beyond-paper", "moe"))
def MOE_EXPERTS(shape: Shape, config: Config, *, interpret: bool = False):
    """The routed experts of a MoE layer: dropless grouped SwiGLU."""
    return make_moe_experts(*_dims(shape), config,
                            dtype=jnp.dtype(shape.get("dtype", "float32")),
                            interpret=interpret)


def lookup_config(N: int, d: int, m: int, E: int, E_held: int, k: int,
                  dtype="float32", profile: DeviceProfile = TPU_V5E,
                  cache: Optional[TuningCache] = None,
                  policy: "AutotunePolicy | str | None" = None
                  ) -> Dict[str, Any]:
    return lookup(MOE_EXPERTS, _shape(N, d, m, E, E_held, k, dtype),
                  profile=profile, cache=cache, policy=policy)


def moe_experts(x: jax.Array, ids: jax.Array, weights: jax.Array,
                wg: jax.Array, wi: jax.Array, wo: jax.Array, *,
                expert_offset=0, num_experts: Optional[int] = None,
                config: Optional[Dict[str, Any]] = None,
                profile: DeviceProfile = TPU_V5E, interpret: bool = False,
                policy: "AutotunePolicy | str | None" = None):
    """For each token of ``x`` (N, d), the weighted sum of the SwiGLU of
    its chosen experts (``ids``, ``weights``: (N, k)) that lie in
    ``[expert_offset, expert_offset + E_held)``, the experts ``wg``, ``wi``
    (E_held, d, m) and ``wo`` (E_held, m, d) hold; zero for a token that
    chose none of them.  ``num_experts`` is how many experts ``ids`` ranges
    over (default: the held ones); the rounds are sized for its balanced
    load, and no pair is dropped whatever the load."""
    N, d = x.shape
    E_held, _, m = wg.shape
    k = ids.shape[1]
    E = num_experts or E_held
    cfg = config or lookup_config(N, d, m, E, E_held, k, x.dtype, profile,
                                  policy=policy)
    fn = make_moe_experts(N, d, m, E, E_held, k, cfg, dtype=x.dtype,
                          interpret=interpret)
    return fn(x, ids, weights, wg, wi, wo, expert_offset=expert_offset)
