"""Machine / program / data parameters (paper §3.1-§3.2).

The paper's machine parameters are hardware resource limits ``R_1..R_s`` and
performance measures ``P_1..P_t``; program/data parameters come from the code
fragment.  All stay *symbolic* through comprehensive optimization and are only
bound when the generated artifact is loaded on a concrete machine.

TPU adaptation (DESIGN.md §2): the binding resources on TPU are VMEM bytes per
core and tile alignment, not registers/threads.  We keep a VREG-pressure
counter as the moral equivalent of the paper's register estimate.
"""
from __future__ import annotations

import enum
import functools
from dataclasses import dataclass
from typing import Any, Dict, Mapping


class ParamKind(enum.Enum):
    MACHINE_RESOURCE = "machine_resource"     # R_i — hardware resource limit
    MACHINE_PERFORMANCE = "machine_perf"      # P_i — performance measure in [0,1]
    PROGRAM = "program"                       # E_i — e.g. block sizes, grain
    DATA = "data"                             # D_i — e.g. matrix order, seq len


@dataclass(frozen=True)
class ParamSymbol:
    name: str
    kind: ParamKind
    doc: str = ""


# --- canonical TPU machine-parameter symbols --------------------------------
VMEM = ParamSymbol("V", ParamKind.MACHINE_RESOURCE,
                   "VMEM bytes available per TensorCore")
VREGS = ParamSymbol("G", ParamKind.MACHINE_RESOURCE,
                    "vector-register budget (lane-values) per core")
CORES = ParamSymbol("CORES", ParamKind.MACHINE_RESOURCE,
                    "number of TensorCores in the slice")
SUBLANE = ParamSymbol("SUBLANE", ParamKind.MACHINE_RESOURCE,
                      "second-minor tile dim (8 for f32, 16 bf16, 32 int8)")
LANE = ParamSymbol("LANE", ParamKind.MACHINE_RESOURCE,
                   "minor tile dim (128)")
MXU = ParamSymbol("MXU", ParamKind.MACHINE_RESOURCE,
                  "systolic array dimension (128)")

OCCUPANCY = ParamSymbol("P_occ", ParamKind.MACHINE_PERFORMANCE,
                        "achievable grid-occupancy ratio")
MXU_UTIL = ParamSymbol("P_mxu", ParamKind.MACHINE_PERFORMANCE,
                       "achievable MXU tile-utilization ratio")

RESOURCE_SYMBOLS = (VMEM, VREGS, CORES, SUBLANE, LANE, MXU)
PERFORMANCE_SYMBOLS = (OCCUPANCY, MXU_UTIL)


@dataclass(frozen=True)
class MachineDescription:
    """Concrete values bound at load time (paper: 'looked up when the
    generated code is loaded on the target machine')."""

    name: str
    vmem_bytes: int
    vreg_budget: int              # lane-values; 2 * 512 VREGs * (8*128) is gen-dep
    num_cores: int
    sublane: int
    lane: int
    mxu: int
    hbm_bytes: int
    hbm_bw: float                 # bytes/s
    peak_flops_bf16: float        # FLOP/s per core-pair (chip)
    ici_bw: float                 # bytes/s per link per chip
    ici_links: int = 4            # v5e 2D torus: 4 links/chip

    def bindings(self) -> Dict[str, int]:
        """Values for the machine symbols used in constraint systems."""
        return {
            VMEM.name: self.vmem_bytes,
            VREGS.name: self.vreg_budget,
            CORES.name: self.num_cores,
            SUBLANE.name: self.sublane,
            LANE.name: self.lane,
            MXU.name: self.mxu,
        }


# TPU v5e (the dry-run / roofline target; constants from the task spec).
TPU_V5E = MachineDescription(
    name="tpu_v5e",
    vmem_bytes=128 * 1024 * 1024,     # ~128 MiB VMEM per core
    vreg_budget=4096,                  # usable f32 lane-rows before spill (est.)
    num_cores=1,                       # per-chip kernels see one TensorCore
    sublane=8,
    lane=128,
    mxu=128,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    peak_flops_bf16=197e12,
    ici_bw=50e9,
    ici_links=4,
)

# A Fermi-class description used only to replay the paper's own case studies
# (Tesla M2050 figures: R registers/thread, T threads/block, Z_B shared words).
PAPER_M2050 = MachineDescription(
    name="paper_m2050",
    vmem_bytes=48 * 1024,              # 48 KiB shared memory / block ~ Z_B
    vreg_budget=63,                    # max registers per thread ~ R
    num_cores=14,                      # SMs
    sublane=1,
    lane=32,                           # warp size
    mxu=1,
    hbm_bytes=3 * 1024**3,
    hbm_bw=148e9,
    peak_flops_bf16=1.03e12,
    ici_bw=8e9,
    ici_links=1,
)

MACHINES: Mapping[str, MachineDescription] = {
    m.name: m for m in (TPU_V5E, PAPER_M2050)
}

# ``jax.Device.device_kind`` -> machine.  A v5e reports "TPU v5 lite".
DEVICE_KINDS: Mapping[str, MachineDescription] = {
    "TPU v5 lite": TPU_V5E,
}


def machine_for(device: Any) -> MachineDescription:
    """The machine whose resources bind the case discussion on ``device``.

    On the CPU backend that is ``TPU_V5E``, the modelled target that tests
    and the dry run select for.  Any other device must be listed in
    ``DEVICE_KINDS``: an unknown accelerator raises, so kernels are never
    picked against another chip's limits."""
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return DEVICE_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no machine description for {device.platform} device kind "
            f"{device.device_kind!r}; add it to repro.core.params."
            f"DEVICE_KINDS") from None


@functools.lru_cache(maxsize=1)
def default_machine() -> MachineDescription:
    """:func:`machine_for` this process's first device (resolved once)."""
    import jax
    return machine_for(jax.devices()[0])
