"""Per-chip hardware constants, keyed by the ``device_kind`` JAX reports.

Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
v5e"): 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect. The host-side fields (PCIe, host FLOP/s and DRAM
bandwidth) are not published peaks: they are the assumptions of the
analytic placement model in ``serving.kvcache``.

A device whose kind is not in ``CHIPS`` is an error, never a default.
"""
from dataclasses import dataclass


@dataclass(frozen=True)
class Chip:
    name: str
    peak_flops_bf16: float      # FLOP/s
    hbm_bandwidth: float        # bytes/s
    hbm_bytes: float            # HBM capacity per chip
    ici_bandwidth: float        # bytes/s chip-to-chip, all links together
    pcie_bandwidth: float       # bytes/s host<->device (placement model)
    host_flops: float           # CPU FLOP/s per host (placement model)
    host_mem_bandwidth: float   # bytes/s host DRAM (placement model)


V5E = Chip(
    name="TPU v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    hbm_bytes=16e9,
    ici_bandwidth=1600e9 / 8,
    pcie_bandwidth=32e9,
    host_flops=3e12,
    host_mem_bandwidth=100e9,
)

# device_kind -> Chip. v5e reports itself as "TPU v5 lite".
CHIPS = {"TPU v5 lite": V5E}


def chip_for(device=None) -> Chip:
    """The table entry of ``device`` (default: the first JAX device)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in CHIPS:
        raise ValueError(f"no hardware entry for device kind {kind!r} "
                         f"(known: {sorted(CHIPS)})")
    return CHIPS[kind]
