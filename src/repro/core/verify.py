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
import numpy as np

# default absolute/relative tolerances per result dtype
_TOLS = {
    jnp.float32.dtype: (1e-5, 1e-5),
    jnp.bfloat16.dtype: (2e-2, 2e-2),
    jnp.float16.dtype: (2e-3, 2e-3),
    jnp.float64.dtype: (1e-12, 1e-12),
}


class VerificationError(AssertionError):
    pass


def _leaf_close(a, b, atol: Optional[float], rtol: Optional[float]) -> None:
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        raise VerificationError(f"shape mismatch: {a.shape} vs {b.shape}")
    if a.dtype != b.dtype:
        # allow dtype promotion differences; compare in f32
        a = a.astype(np.float32)
        b = b.astype(np.float32)
    da, dr = _TOLS.get(jnp.asarray(a).dtype, (1e-5, 1e-5))
    atol = da if atol is None else atol
    rtol = dr if rtol is None else rtol
    # normwise: rtol scales with the reference's largest magnitude.  An
    # output of a long reduction can sit near zero while its rounding error
    # scales with the terms summed; elementwise, two exact float32
    # summation orders of a 2048^3 GEMM already disagree on ~0.6% of
    # elements at rtol 1e-5.
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    err = np.abs(a64 - b64)
    scale = float(np.abs(b64).max()) if b64.size else 0.0
    if not np.all(err <= atol + rtol * scale):   # NaN anywhere fails too
        raise VerificationError(
            f"output mismatch: max_abs_err={np.nanmax(err):.3e} "
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
