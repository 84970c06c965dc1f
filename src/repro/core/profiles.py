"""Device profiles: the TPU analogue of CLTune's per-device limits.

CLTune queries the OpenCL runtime for device limits (max workgroup size,
local-memory bytes, ...) and auto-imposes them as search-space constraints
(paper section III-A).  On TPU the corresponding limits are the VMEM byte
budget, the MXU systolic-array tile (128x128) and the VPU sublane/lane
geometry.  A :class:`DeviceProfile` carries those limits plus the peak
compute / bandwidth numbers the analytical and roofline evaluators need.

The four profiles below play the role of the paper's four GPUs
(K40m / GTX480 / HD7970 / Iris 5100): architecturally diverse devices used
to demonstrate that best-found parameters are device specific.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

GiB = 1024**3
MiB = 1024**2


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Static description of one accelerator chip (single core view)."""

    name: str
    #: peak dense matmul throughput, FLOP/s (bf16 unless noted)
    peak_flops: float
    #: main-memory (HBM) bandwidth, bytes/s
    hbm_bw: float
    #: HBM capacity per chip, bytes
    hbm_bytes: int
    #: usable VMEM (vector memory) per core, bytes.  This is the "local
    #: memory size" auto-constraint of the paper.
    vmem_bytes: int
    #: MXU systolic tile edge (lanes); matmul operands want multiples of this
    mxu_dim: int = 128
    #: VPU sublane count for float32; bf16 packs 2x, int8 4x
    sublanes_f32: int = 8
    #: inter-chip-interconnect bandwidth per link, bytes/s
    ici_bw: float = 50e9
    #: number of ICI links per chip (2D torus: 4)
    ici_links: int = 4
    #: scalar-unit overhead per grid step, seconds (pipeline bubble model)
    grid_step_overhead: float = 1.0e-7
    #: kernel launch / dispatch fixed overhead, seconds
    launch_overhead: float = 2.0e-6

    # -- derived helpers ---------------------------------------------------
    def sublanes(self, dtype_bytes: int) -> int:
        """Minimum second-minor tile dimension for a dtype (8/16/32)."""
        return self.sublanes_f32 * max(1, 4 // max(1, dtype_bytes))

    def fits_vmem(self, nbytes: int) -> bool:
        """Whether a declared working-set footprint fits this core's VMEM.

        This is the paper's local-memory auto-constraint as a device
        method: ``repro.analyze`` proves configs infeasible with it, and
        a footprint exactly at the budget *fits* (the budget is usable
        bytes, not a strict bound)."""
        return nbytes <= self.vmem_bytes

    @property
    def flops_per_byte(self) -> float:
        """Machine balance: FLOPs available per HBM byte moved."""
        return self.peak_flops / self.hbm_bw


# ---------------------------------------------------------------------------
# Profiles.  v5e is the TARGET device of this reproduction (numbers match the
# roofline constants mandated by the brief).  The other three provide the
# cross-device portability study in benchmarks (paper Tables II/IV).
# ---------------------------------------------------------------------------

TPU_V5E = DeviceProfile(
    name="tpu_v5e",
    peak_flops=197e12,        # bf16
    hbm_bw=819e9,
    hbm_bytes=16 * GiB,
    vmem_bytes=128 * MiB,
    ici_bw=50e9,
    ici_links=4,
)

TPU_V4 = DeviceProfile(
    name="tpu_v4",
    peak_flops=275e12,
    hbm_bw=1228e9,
    hbm_bytes=32 * GiB,
    vmem_bytes=128 * MiB,
    ici_bw=100e9,
    ici_links=6,
)

TPU_V5P = DeviceProfile(
    name="tpu_v5p",
    peak_flops=459e12,
    hbm_bw=2765e9,
    hbm_bytes=95 * GiB,
    vmem_bytes=128 * MiB,
    ici_bw=100e9,
    ici_links=6,
)

TPU_V3 = DeviceProfile(
    name="tpu_v3",
    peak_flops=123e12,
    hbm_bw=900e9,
    hbm_bytes=16 * GiB,
    vmem_bytes=16 * MiB,     # much smaller VMEM: shifts best tile sizes down,
    ici_bw=70e9,             # the way Iris 5100's low bandwidth shifted params
    ici_links=4,
)

PROFILES: Dict[str, DeviceProfile] = {
    p.name: p for p in (TPU_V5E, TPU_V4, TPU_V5P, TPU_V3)
}

#: ``device_kind`` as JAX reports it on an attached chip -> its profile.
#: Only chips this code has run on are listed; the other profiles are
#: modeled and have no attached-device entry.
DEVICE_KINDS: Dict[str, DeviceProfile] = {
    "TPU v5 lite": TPU_V5E,
}


def profile_for_kind(device_kind: str) -> DeviceProfile:
    """The profile of an attached chip, by its ``device_kind``.

    An unknown kind raises: timing a chip against another chip's limits
    would prune and record under the wrong device."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError as e:
        raise KeyError(
            f"no device profile for device_kind {device_kind!r}; "
            f"known: {sorted(DEVICE_KINDS)}") from e


def attached_profile() -> DeviceProfile:
    """The profile of the chip JAX runs on (its first device).

    Raises on a non-TPU backend, naming the platform, and on a TPU whose
    ``device_kind`` has no profile."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU attached: JAX's default platform is {dev.platform!r}")
    return profile_for_kind(dev.device_kind)


def kernel_vmem_limit() -> int:
    """``vmem_limit_bytes`` for every Pallas kernel's compile.

    On a TPU backend: the attached chip's VMEM budget, the one
    ``repro.analyze`` proves footprints against for that chip's profile
    (an unknown ``device_kind`` raises, as in :func:`attached_profile`).
    On a host backend a compiled (non-interpret) build can only be an
    ahead-of-time compile for a described topology; that target is
    ``TPU_V5E``, the one chip in :data:`DEVICE_KINDS`."""
    import jax

    if jax.default_backend() == "tpu":
        return attached_profile().vmem_bytes
    return TPU_V5E.vmem_bytes


def get_profile(name: str) -> DeviceProfile:
    try:
        return PROFILES[name]
    except KeyError as e:
        raise KeyError(
            f"unknown device profile {name!r}; known: {sorted(PROFILES)}"
        ) from e
