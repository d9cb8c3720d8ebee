"""Accelerator peak rates for roofline analysis and kernel sizing.

The program runs on TPU v5e; tests run on the CPU. Peaks are keyed by
the ``device_kind`` JAX reports (:data:`CHIPS`), and a kind the table
does not list is an error (:func:`chip_spec`), never a default. All
roofline terms in benchmarks/ and launch/dryrun.py are derived from
these numbers, so they live in one place.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip capability of the target accelerator."""

    name: str
    peak_bf16_flops: float      # FLOP/s
    hbm_bandwidth: float        # B/s
    ici_link_bandwidth: float   # B/s per link (one direction)
    ici_links: int              # links per chip (2D torus on v5e)
    hbm_bytes: int              # capacity
    vmem_bytes: int             # on-chip vector memory


# Published peaks of one TPU v5e chip (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
# of chip-to-chip interconnect (4 links of 50 GB/s). VMEM 128 MiB.
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

# Keyed by ``jax.devices()[i].device_kind``.
CHIPS: dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


def chip_spec(device_kind: str) -> ChipSpec:
    """Peak rates of ``device_kind``; raises ``ValueError`` on a kind
    :data:`CHIPS` does not list."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; "
            f"known kinds: {sorted(CHIPS)}"
        ) from None


# MXU native tile — matmul dims should be multiples of this.
MXU_DIM = 128
# VPU lane structure: (sublanes, lanes) for fp32.
VPU_SUBLANES = 8
VPU_LANES = 128


def roofline_terms(
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    n_chips: int,
    chip: ChipSpec,
) -> dict[str, float]:
    """The three roofline terms (seconds) per the methodology in DESIGN.md §6.

    ``hlo_flops``/``hlo_bytes`` are the *per-device* numbers XLA reports from
    ``compiled.cost_analysis()`` (cost_analysis is per-participant under SPMD);
    ``collective_bytes`` is the per-device sum of collective operand bytes
    parsed from the HLO text. The division by ``n_chips`` is therefore already
    implicit; we keep the interface in global terms and divide here so callers
    can pass either convention via ``n_chips=1`` (per-device inputs) or the
    actual chip count (global inputs).
    """
    return {
        "compute_s": hlo_flops / (n_chips * chip.peak_bf16_flops),
        "memory_s": hlo_bytes / (n_chips * chip.hbm_bandwidth),
        "collective_s": collective_bytes / (n_chips * chip.ici_link_bandwidth),
    }
