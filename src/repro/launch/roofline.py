"""Three-term roofline from a compiled step's static HLO walk.

Peaks are per chip and keyed by ``jax.Device.device_kind``; a device that is
not in :data:`PEAKS` gets no roofline (``None``, reported "not measured"),
never another chip's numbers.

  compute term    = FLOPs_per_device            / peak_FLOPs
  memory term     = HBM_bytes_per_device        / HBM_bw
  collective term = collective_bytes_per_device / link_bw

FLOPs / HBM bytes / collective bytes come from launch.hlo_analysis (the
while-trip-count-corrected static walk of the compiled module — XLA's raw
``cost_analysis()`` counts a while body once and so underestimates any
scanned count step by ~trip-count×; see the hlo_analysis module docstring).

The miner's useful-FLOPs estimate (2·n·items·K/256 packed word ops) lives in
``launch.mine_dryrun``; the ratio useful / HLO_FLOPs catches padding and
dispatch overhead.
"""

from __future__ import annotations

import dataclasses

# Google Cloud, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links (50 GB/s each)
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
V5E = "TPU v5 lite"


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def compute_fraction(self) -> float:
        """How close the step is to pure-compute roofline: compute / bound."""
        return self.compute_s / max(self.bound_s, 1e-30)


def roofline_terms(flops_per_dev: float, hbm_bytes_per_dev: float,
                   coll_bytes_per_dev: float, device_kind: str) -> Roofline | None:
    """The three terms on ``device_kind``'s peaks; None for an unknown device."""
    peaks = PEAKS.get(device_kind)
    if peaks is None:
        return None
    return Roofline(
        compute_s=flops_per_dev / peaks["flops"],
        memory_s=hbm_bytes_per_dev / peaks["hbm_bw"],
        collective_s=coll_bytes_per_dev / peaks["ici_bw"],
    )
