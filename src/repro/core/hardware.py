"""Hardware descriptors — the TPU analogue of SVE's runtime vector-length query.

The paper's central premise is that the hardware vector length ``VL`` is a
*runtime* constant (``svcntw()``), not a compile-time constant, and that data
layouts must therefore be *functions of a hardware descriptor* rather than
baked-in numbers.  On TPU the corresponding implementation-defined parameters
are the lane count of the vector/matrix units, the sublane depth, the dtype
packing factor, and the MXU contraction depth.  This module is the single
place those parameters are queried; everything else in the framework treats
them symbolically (via :class:`HardwareSpec`), exactly as the paper treats
``VL``.

``presets`` additionally contains *scaled* variants (``tpu_vl256``,
``tpu_vl512``) used by the Fig-3-analogue scaling study: the same layout and
kernel code instantiated at a wider "vector length", mirroring the paper's
gem5 SVE-128/256/512 experiment.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "HardwareSpec",
    "presets",
    "TPU_KINDS",
    "query",
    "dtype_bits",
    "sublane_packing",
]


def dtype_bits(dtype) -> int:
    """Bit width of an element of ``dtype``."""
    return np.dtype(jnp.dtype(dtype)).itemsize * 8


def sublane_packing(dtype) -> int:
    """How many elements of ``dtype`` pack into one 32-bit sublane word.

    This is the TPU analogue of "more SVE elements per vector for narrower
    types": fp32 native tiles are (8,128); bf16 (16,128); int8/fp8 (32,128).
    """
    return max(1, 32 // dtype_bits(dtype))


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Implementation-defined hardware parameters (the ``VL`` of the paper).

    Attributes:
      name: preset identifier.
      lanes: minor-dim lane count of the vector unit.  The direct analogue of
        the paper's ``VL`` (in elements).  128 on all shipped TPUs; the
        scaling-study presets widen it.
      sublanes: native sublane count for a 32-bit element (8 on TPU).
      mxu_k: contraction depth of the systolic array (granularity at which
        the MXU consumes the K dimension).
      vmem_bytes: per-core VMEM capacity (drives BlockSpec sizing).
      hbm_bw: HBM bandwidth, bytes/s/chip (roofline memory term).
      flops_bf16 / flops_f32: peak FLOP/s per chip.
      ici_bw: inter-chip link bandwidth, bytes/s/link (roofline collective
        term).
      hbm_bytes: HBM capacity per chip.
    """

    name: str
    lanes: int = 128
    sublanes: int = 8
    mxu_k: int = 128
    vmem_bytes: int = 16 * 2**20
    hbm_bw: float = 819e9
    flops_bf16: float = 197e12
    flops_f32: float = 98.5e12
    ici_bw: float = 50e9
    hbm_bytes: int = 16 * 2**30

    def vl(self, dtype=jnp.float32) -> int:
        """Vector length in elements (minor dim) — the ``svcntw()`` analogue.

        On TPU the minor (lane) dim is dtype-independent; dtype width shows
        up as sublane packing instead (see :func:`sublane_packing`).
        """
        del dtype
        return self.lanes

    def native_tile(self, dtype) -> tuple[int, int]:
        """The native (second-minor, minor) memory tile for ``dtype``."""
        return (self.sublanes * sublane_packing(dtype), self.lanes)

    def peak_flops(self, dtype) -> float:
        return self.flops_f32 if dtype_bits(dtype) >= 32 else self.flops_bf16

    def scaled(self, factor: int) -> "HardwareSpec":
        """A hypothetical implementation with ``factor``× wider vectors.

        Used by the VL-scaling study: like moving SVE-128 → SVE-512, compute
        throughput scales with width while memory bandwidth does not.
        """
        return dataclasses.replace(
            self,
            name=f"{self.name}_vl{self.lanes * factor}",
            lanes=self.lanes * factor,
            mxu_k=self.mxu_k * factor,
            flops_bf16=self.flops_bf16 * factor,
            flops_f32=self.flops_f32 * factor,
        )


# TPU v5e is the primary target.  Peaks are Google Cloud's published
# figures for one v5e chip ("TPU v5e" documentation): 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect over four links.
_TPU_V5E = HardwareSpec(name="tpu_v5e")

presets: dict[str, HardwareSpec] = {
    "tpu_v5e": _TPU_V5E,
    # v4-like: bigger VMEM, different peak -- demonstrates portability of the
    # layout code across generations (same lanes, different everything else).
    "tpu_v4": HardwareSpec(
        name="tpu_v4",
        vmem_bytes=32 * 2**20,
        hbm_bw=1228e9,
        flops_bf16=275e12,
        flops_f32=137.5e12,
        hbm_bytes=32 * 2**30,
    ),
    # Scaling-study presets (Fig 3 analogue): hypothetical wider-vector
    # implementations.  Only lane count / MXU depth / peak FLOPs change, the
    # memory system is held fixed -- the same controlled experiment as the
    # paper's gem5 study (which scaled only the vector width).
    "tpu_vl128": _TPU_V5E,
    "tpu_vl256": _TPU_V5E.scaled(2),
    "tpu_vl512": _TPU_V5E.scaled(4),
}


# ``jax.devices()[0].device_kind`` -> preset, for the chips whose peaks this
# module holds.  A TPU that is not listed is an error, never a default.
TPU_KINDS: dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def query(name: Optional[str] = None) -> HardwareSpec:
    """Query the hardware descriptor at run time (``svcntw()`` analogue).

    Resolution order: explicit ``name`` → ``$REPRO_HW`` → the attached TPU's
    ``device_kind`` through :data:`TPU_KINDS` (a kind not in the table
    raises).  Off TPU the modelled v5e is returned: it sizes the packed
    layouts and kernel blocks the CPU tests check, and its peaks price no
    measurement.
    """
    if name is None:
        name = os.environ.get("REPRO_HW")
    if name is not None:
        if name not in presets:
            raise KeyError(f"unknown hardware preset {name!r}; have {sorted(presets)}")
        return presets[name]
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        kind = dev.device_kind
        if kind not in TPU_KINDS:
            raise KeyError(f"TPU device_kind {kind!r} has no hardware preset; "
                           f"known kinds: {sorted(TPU_KINDS)}")
        return presets[TPU_KINDS[kind]]
    return presets["tpu_v5e"]
