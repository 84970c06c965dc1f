"""Result verification: CLTune's ``SetReference`` mechanism.

The outputs of each tested kernel configuration are compared against the
outputs of a reference implementation; a mismatch marks the configuration as
failed so "no parameter-dependent bugs are present in the kernel"
(paper section III-A).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

# default absolute/relative tolerances per result dtype
_TOLS = {
    jnp.float32.dtype: (1e-5, 1e-5),
    jnp.bfloat16.dtype: (2e-2, 2e-2),
    jnp.float16.dtype: (2e-3, 2e-3),
    jnp.float64.dtype: (1e-12, 1e-12),
}


class VerificationError(AssertionError):
    pass


@jax.jit
def _errors(a, b):
    """``max|a - b|`` (NaN ignored), ``max|b|`` and whether any element of
    ``a - b`` is not finite, reduced where the arrays live."""
    wide = jnp.promote_types(jnp.promote_types(a.dtype, b.dtype),
                             jnp.float32)
    d = a.astype(wide) - b.astype(wide)
    return (jnp.nanmax(jnp.abs(d)), jnp.max(jnp.abs(b.astype(wide))),
            ~jnp.all(jnp.isfinite(d)))


def _leaf_close(a, b, atol: Optional[float], rtol: Optional[float]) -> None:
    """Normwise check of one leaf: ``max|a - b| <= atol + rtol * max|b|``,
    failing on any NaN or inf in ``a - b``.

    Both leaves become ``jax.Array``s (a host leaf is uploaded, a float64
    one held as float32 unless x64 is on) and are reduced on the device to
    three scalars; only those reach the host, where the rule is applied in
    float64.  The difference is formed in the wider of the leaves' dtype
    and float32 and rounded once, so a verdict can differ from an exact
    one only where the error lies within one float32 rounding (2**-24
    relative) of the threshold.  The tolerances are the leaves' dtype's,
    or float32's where the two dtypes differ."""
    # normwise: rtol scales with the reference's largest magnitude.  An
    # output of a long reduction can sit near zero while its rounding error
    # scales with the terms summed; elementwise, two exact float32
    # summation orders of a 2048^3 GEMM already disagree on ~0.6% of
    # elements at rtol 1e-5.
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape:
        raise VerificationError(f"shape mismatch: {a.shape} vs {b.shape}")
    if a.size == 0:
        return
    da, dr = _TOLS.get(a.dtype if a.dtype == b.dtype else jnp.float32.dtype,
                       (1e-5, 1e-5))
    atol = da if atol is None else atol
    rtol = dr if rtol is None else rtol
    err, scale, nonfinite = (float(x) for x in jax.device_get(_errors(a, b)))
    if nonfinite or not err <= atol + rtol * scale:
        raise VerificationError(
            f"output mismatch: max_abs_err={err:.3e} "
            f"max|ref|={scale:.3e} (atol={atol}, rtol={rtol} of max|ref|)")


def assert_trees_close(candidate: Any, reference: Any,
                       atol: Optional[float] = None,
                       rtol: Optional[float] = None) -> None:
    """Assert two pytrees of arrays match within tolerance."""
    ca = jax.tree_util.tree_leaves(candidate)
    re_ = jax.tree_util.tree_leaves(reference)
    if len(ca) != len(re_):
        raise VerificationError(
            f"pytree leaf count mismatch: {len(ca)} vs {len(re_)}")
    for a, b in zip(ca, re_):
        _leaf_close(a, b, atol, rtol)


def trees_close(candidate: Any, reference: Any,
                atol: Optional[float] = None,
                rtol: Optional[float] = None) -> bool:
    try:
        assert_trees_close(candidate, reference, atol=atol, rtol=rtol)
        return True
    except VerificationError:
        return False
