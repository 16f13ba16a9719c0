"""repro.arch — the unified device-capability layer.

One declarative :class:`DeviceSpec` per accelerator carries everything the
simulator, HLO bridge, roofline and what-if sweeps need: compute topology,
MFMA cycle tables with validation provenance, memory-hierarchy latencies
*and* bandwidths, interconnect, clocks and advertised peaks.

  spec      — the DeviceSpec schema (+ MemoryHierarchy / Interconnect)
  registry  — the device catalog (mi200, mi300, mi300x, tpu_v5e, tpu_v5p)
  overlay   — composable what-if scenario transforms + sweep grids
  select    — instruction-selection policy (best MFMA per dtype)

Consumers: ``repro.core.machine`` (thin execution facade),
``repro.core.isa`` (instruction registry; legacy table views),
``repro.launch.roofline`` (peaks/bandwidths), ``repro.core.whatif``
(overlay sweeps).  To add a device, see ROADMAP.md "Architecture".
"""

from repro.arch.overlay import IDENTITY, Overlay, overlay_grid  # noqa: F401
from repro.arch.registry import (UnknownDeviceError,  # noqa: F401
                                 device_for_kind, get_device, list_devices,
                                 register_device)
from repro.arch.select import (HLO_DTYPE_TO_IN, best_mfma,  # noqa: F401
                               best_mfma_for_hlo, throughput_ranking)
from repro.arch.spec import (CycleEntry, DeviceSpec,  # noqa: F401
                             Interconnect, MemoryHierarchy)

__all__ = [
    "CycleEntry", "DeviceSpec", "Interconnect", "MemoryHierarchy",
    "Overlay", "IDENTITY", "overlay_grid",
    "UnknownDeviceError", "device_for_kind", "get_device", "list_devices",
    "register_device",
    "HLO_DTYPE_TO_IN", "best_mfma", "best_mfma_for_hlo",
    "throughput_ranking",
]
