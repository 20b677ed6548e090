"""Roofline math for the dry-run analysis (§Roofline).

Hardware model: one table entry per chip, keyed by JAX's ``device_kind``
(``CHIPS``). ``device_hw()`` picks the entry for the chip the program runs
on; planning off the chip (CPU tests, the dry-run) targets the v5e entry:
  peak bf16 compute : 197 TFLOP/s per chip
  HBM bandwidth     : 819 GB/s per chip
  ICI link bandwidth: ~50 GB/s per link (we use per-chip aggregate = 1 link
                      as the conservative spec-mandated constant)
  ICI link latency  : ~1 us per collective launch (the α in the α + β·b
                      transfer model; core/cost_model.py charges it per
                      message so many small collectives cost more than one
                      fused one — the term the gradient bucketing removes)

Conventions (documented because the spec formula mixes global/per-chip):
  * ``cost_analysis()`` on the compiled (post-SPMD) module reports *per-chip*
    FLOPs and bytes. We multiply by chip count to get the global numbers the
    spec formula expects; the resulting *term* is then per-step seconds on the
    critical path of one chip, identical either way.
  * collective_bytes from the HLO parser is per-chip; the collective term is
    per_chip_collective_bytes / link_bw.
"""
from __future__ import annotations

from dataclasses import dataclass, asdict


@dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12      # bf16 FLOP/s per chip
    hbm_bw: float = 819e9           # bytes/s per chip
    link_bw: float = 50e9           # bytes/s per chip (ICI, intra-host β₁)
    hbm_bytes: float = 16e9         # HBM capacity per chip
    vmem_bytes: float = 16e6        # on-chip vector memory per core
    link_latency: float = 1e-6      # s per collective message (intra α₁)
    # inter-host tier (DCN): None = single-tier fabric — every collective is
    # priced at (link_latency, link_bw) and the cost model reduces exactly to
    # the flat α + β·b of the paper era. Set both (e.g. from a fitted
    # hw_profile.json, tools/profile_collectives.py) to let the planner price
    # two-level reduce-scatter→all-gather schedules on multi-host meshes.
    inter_bw: float | None = None       # bytes/s per chip across hosts (β₂)
    inter_latency: float | None = None  # s per cross-host message (α₂)

    @property
    def hierarchical(self) -> bool:
        return self.inter_bw is not None and self.inter_latency is not None


# Per-chip peaks keyed by ``jax.Device.device_kind``. TPU v5e ("TPU v5
# lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s. The ICI and latency terms are the conservative planner
# constants above, not published figures.
CHIPS = {"TPU v5 lite": Hardware(name="tpu-v5e")}

# the named target when planning off the chip
HW = CHIPS["TPU v5 lite"]


def device_hw() -> Hardware:
    """The hardware model of the chip JAX runs on.

    On a TPU backend, the ``CHIPS`` entry for the device's ``device_kind``:
    a kind with no entry raises instead of pricing against another chip's
    peaks. On any other backend (CPU planning and tests), the v5e target.
    """
    import jax
    if jax.default_backend() != "tpu":
        return HW
    kind = jax.devices()[0].device_kind
    if kind not in CHIPS:
        raise KeyError(
            f"no hardware model for TPU device_kind {kind!r}: add its "
            f"published peaks to utils/roofline.CHIPS (known: "
            f"{sorted(CHIPS)})")
    return CHIPS[kind]

# TPU vector-lane width: Pallas blocks tile the last dim in multiples of this
LANE = 128


def kernel_tile_candidates(e: int, itemsize: int, hw: Hardware = HW,
                           lane: int = LANE) -> list[int]:
    """Feature-tile (block_e) candidates for the embedding kernels.

    Multiples of the lane width that divide E exactly (anything else pads or
    misaligns) and whose double-buffered block fits comfortably in VMEM.
    0 — the fixed full-row block — is always a candidate, so a measured
    argmin over this list can never lose to the untuned default.
    """
    cands = [0]
    for be in range(lane, e, lane):
        if e % be == 0 and 2 * be * itemsize <= hw.vmem_bytes:
            cands.append(be)
    return cands


def embed_tile_seconds(n: int, e: int, block_e: int, itemsize: int,
                       hw: Hardware = HW, step_overhead: float = 2e-7
                       ) -> float:
    """Roofline estimate for one embed gather/scatter sweep: the row bytes
    always cross HBM once; tiling only adds grid steps (each with a fixed
    issue/DMA-setup overhead) while shrinking the per-step VMEM block. The
    autotuner uses this to *rank* candidates before measuring — the measured
    argmin decides, the model just prunes the sweep."""
    be = block_e if block_e and block_e < e and e % block_e == 0 else e
    steps = n * (e // be)
    return n * e * itemsize / hw.hbm_bw + steps * step_overhead


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    per_chip_flops: float
    per_chip_hbm_bytes: float
    per_chip_collective_bytes: float
    model_flops_global: float       # 6*N*D (dense) or 6*N_active*D (MoE)
    per_chip_peak_memory: float     # from memory_analysis()
    collective_breakdown: dict | None = None

    @property
    def compute_s(self) -> float:
        return self.per_chip_flops / HW.peak_flops

    @property
    def memory_s(self) -> float:
        return self.per_chip_hbm_bytes / HW.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.per_chip_collective_bytes / HW.link_bw

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
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global). Catches remat/redundancy waste."""
        hlo_global = self.per_chip_flops * self.chips
        if hlo_global <= 0:
            return 0.0
        return self.model_flops_global / hlo_global

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / critical-path bound — the score metric.

        = (MODEL_FLOPS / (chips*peak)) / max(compute_s, memory_s, coll_s)
        """
        ideal = self.model_flops_global / (self.chips * HW.peak_flops)
        b = self.bound_s
        return ideal / b if b > 0 else 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            compute_s=self.compute_s,
            memory_s=self.memory_s,
            collective_s=self.collective_s,
            dominant=self.dominant,
            useful_flops_fraction=self.useful_flops_fraction,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def roofline_from_analysis(
    *,
    arch: str,
    shape: str,
    mesh: str,
    chips: int,
    cost: dict,
    collective_bytes: int,
    model_flops_global: float,
    peak_memory: float,
    collective_breakdown: dict | None = None,
) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    return RooflineTerms(
        arch=arch,
        shape=shape,
        mesh=mesh,
        chips=chips,
        per_chip_flops=flops,
        per_chip_hbm_bytes=hbm,
        per_chip_collective_bytes=float(collective_bytes),
        model_flops_global=model_flops_global,
        per_chip_peak_memory=float(peak_memory),
        collective_breakdown=collective_breakdown,
    )
