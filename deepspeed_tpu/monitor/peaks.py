"""Chip peak table — the ONE place hardware ceilings live.

``bench.py`` used to own a private ``TPU_PEAK_TFLOPS`` dict for its
utilisation denominator; the roofline cost model (cost_model.py), the
environment report, and the bench gate all need the same numbers, so the
table lives here and everyone imports it.

The figures are rough public per-chip specs by TPU generation:

- ``bf16_tflops``: dense bf16/int8-class matmul peak (the MXU ceiling and
  the MFU denominator);
- ``hbm_gbs``: HBM bandwidth, GB/s (the memory-roofline ceiling);
- ``ici_gbs``: aggregate inter-chip interconnect bandwidth per chip, GB/s
  one-way (the communication-roofline ceiling for ring collectives
  WITHIN one slice).
- ``dcn_gbs``: per-chip share of the host's data-center-network NIC,
  GB/s one-way — the SECOND communication tier, what inter-slice
  collectives ride in a multislice deployment. These are rough
  deployment-dependent figures (host NIC bandwidth divided by chips per
  host), one to two orders of magnitude below ICI — which is the whole
  point of the hierarchical sync: the two tiers must be priced
  separately or the roofline lies (a step can be DCN-bound while ICI
  idles).

They are CEILINGS for roofline verdicts and utilisation fractions, not
measurements — real programs see lower effective bandwidth (stride
patterns, link contention), and the DCN column doubly so (it depends on
the NIC provisioning of the actual pod).

Rows are found by the ``device_kind`` string the hardware reports
(``"TPU v5 lite"`` is a v5e).  A TPU kind with no row RAISES: a
utilisation against a guessed peak on a real chip is a wrong number,
not a default.  Non-TPU platforms (the CPU dev mesh) have no
meaningful peak; they get the v5e row flagged ``assumed=True`` so the
cost model's arithmetic stays total-ordered in CPU tests, and nothing
that prints a utilisation may use an assumed row.

Sources: Google Cloud TPU documentation, the per-generation system
architecture pages ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e"): peak
bf16 compute, HBM bandwidth and inter-chip interconnect bandwidth per
chip (ICI published in Gbit/s: 2400 / 1600 / 4800 / 3584).
"""
from __future__ import annotations

import dataclasses
from typing import Dict


# bf16 peak TFLOPs per chip by TPU generation (sources in the module
# docstring); the utilisation denominator.
TPU_PEAK_TFLOPS: Dict[str, float] = {
    "v4": 275.0, "v5e": 197.0, "v5p": 459.0, "v6e": 918.0,
}

# HBM bandwidth GB/s per chip (public figures, same generations).
TPU_HBM_GBS: Dict[str, float] = {
    "v4": 1228.0, "v5e": 819.0, "v5p": 2765.0, "v6e": 1640.0,
}

# Aggregate one-way ICI bandwidth GB/s per chip (public per-chip
# interconnect figures: 2400/1600/4800/3584 Gbps).
TPU_ICI_GBS: Dict[str, float] = {
    "v4": 300.0, "v5e": 200.0, "v5p": 600.0, "v6e": 448.0,
}

# Per-chip share of the host DCN NIC, GB/s one-way: rough figures from
# ~100-200 Gbps host NICs over 4-8 chips per host (deployment-dependent
# — these are two-tier-roofline ceilings for the inter-slice hop, not
# specs; a real pod's provisioning should overwrite the verdict with a
# measured figure). Note the ratio to ICI: 30-60x slower per chip.
TPU_DCN_GBS: Dict[str, float] = {
    "v4": 6.25, "v5e": 6.25, "v5p": 12.5, "v6e": 12.5,
}

_DEFAULT_GEN = "v5e"

# ``jax.Device.device_kind`` as the hardware reports it (lower-cased) ->
# generation row.  Both spellings JAX itself knows are listed.
DEVICE_KIND_TO_GEN: Dict[str, str] = {
    "tpu v4": "v4",
    "tpu v5 lite": "v5e", "tpu v5e": "v5e",
    "tpu v5": "v5p", "tpu v5p": "v5p",
    "tpu v6 lite": "v6e", "tpu v6e": "v6e",
}


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Per-chip hardware ceilings for one device generation."""
    name: str                  # resolved generation key, e.g. "v5e"
    bf16_tflops: float
    hbm_gbs: float
    ici_gbs: float
    dcn_gbs: float = TPU_DCN_GBS["v5e"]
    assumed: bool = False      # True when the device kind had no table row

    @property
    def flops_per_sec(self) -> float:
        return self.bf16_tflops * 1e12

    @property
    def hbm_bytes_per_sec(self) -> float:
        return self.hbm_gbs * 1e9

    @property
    def ici_bytes_per_sec(self) -> float:
        return self.ici_gbs * 1e9

    @property
    def dcn_bytes_per_sec(self) -> float:
        return self.dcn_gbs * 1e9

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


def peaks_for_kind(device_kind: str) -> ChipPeaks:
    """ChipPeaks for a ``device_kind`` string (or a bare generation key
    such as ``"v5e"``).  A TPU kind without a row raises; non-TPU kinds
    (CPU, GPU) get the v5e row flagged ``assumed``."""
    kind = (device_kind or "").strip().lower()
    gen = DEVICE_KIND_TO_GEN.get(kind) or \
        (kind if kind in TPU_PEAK_TFLOPS else None)
    if gen is None and kind.startswith("tpu"):
        raise KeyError(
            f"no peak row for TPU device_kind {device_kind!r}; add it to "
            f"monitor/peaks.py with its source (known: "
            f"{sorted(DEVICE_KIND_TO_GEN)})")
    key, assumed = (gen, False) if gen else (_DEFAULT_GEN, True)
    return ChipPeaks(name=key, bf16_tflops=TPU_PEAK_TFLOPS[key],
                     hbm_gbs=TPU_HBM_GBS[key], ici_gbs=TPU_ICI_GBS[key],
                     dcn_gbs=TPU_DCN_GBS[key], assumed=assumed)


def chip_peaks(device=None) -> ChipPeaks:
    """ChipPeaks of ``device`` (default: the first visible device)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return peaks_for_kind(getattr(device, "device_kind", ""))


def chip_peak_tflops() -> float:
    """bf16 peak TFLOPs of the first visible chip — the utilisation
    denominator.  Raises off-TPU: there is no peak to divide by."""
    pk = chip_peaks()
    if pk.assumed:
        raise RuntimeError(
            "no chip peak on this platform (the v5e row is only ASSUMED "
            "here); a utilisation is printed from a real TPU's row or "
            "not at all")
    return pk.bf16_tflops


__all__ = ["TPU_PEAK_TFLOPS", "TPU_HBM_GBS", "TPU_ICI_GBS", "TPU_DCN_GBS",
           "DEVICE_KIND_TO_GEN", "ChipPeaks", "peaks_for_kind", "chip_peaks", "chip_peak_tflops"]
