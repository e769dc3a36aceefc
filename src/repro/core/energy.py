"""Analytical cost / memory-access / energy models (paper Figs 2-3, Tables 3-4).

The paper measures latency with a scope and energy with a current shunt; on
a TPU target neither exists, so the framework carries first-principles
models with the SAME structure the paper validates empirically:

  * theoretical MACs / params per primitive   -> Table 1 (ConvSpec methods)
  * memory accesses, direct vs im2col-blocked -> Fig 3 ratio
  * MCU latency & power vs frequency          -> Fig 4 / Table 3
  * energy = P(f) * latency                   -> Fig 2 c/e
  * TPU v5e energy terms (per roofline op)    -> EXPERIMENTS.md §Roofline

MCU constants are calibrated to the paper's own Table 3 (linear fit of the
reported mW at 10/20/40/80 MHz); they reproduce the paper's headline claims
(MACs<->energy linearity without SIMD; latency as the better predictor with
SIMD) inside the model, which the benchmark harness then demonstrates.
"""
from __future__ import annotations

import dataclasses

from .primitives import ConvSpec

# --------------------------------------------------------------------------
# Memory-access model (element accesses for scalar path, 32-bit word accesses
# for the SIMD path — what the Cortex-M actually issues).
# --------------------------------------------------------------------------


def patch_len(spec: ConvSpec) -> int:
    """im2col column length K for the primitive's matmul stage."""
    if spec.primitive in ("standard", "add"):
        return spec.kernel_size ** 2 * spec.in_channels
    if spec.primitive == "grouped":
        return spec.kernel_size ** 2 * (spec.in_channels // spec.groups)
    if spec.primitive in ("dws", "shift"):
        return spec.in_channels          # pointwise stage
    raise AssertionError


def accesses_direct(spec: ConvSpec, out_width: int) -> int:
    """Scalar loop: 2 loads per MAC + 1 store per output element.

    For dws, depthwise and pointwise stages both follow the same pattern.
    For shift, the shift stage is 1 load + 1 store per input element.
    """
    hy2 = out_width ** 2
    macs = spec.mac_count(out_width)
    stores = hy2 * spec.out_channels
    extra = 0
    if spec.primitive == "dws":
        stores += hy2 * spec.in_channels           # intermediate map
    if spec.primitive == "shift":
        extra = 2 * hy2 * spec.in_channels         # shift copy in/out
    return 2 * macs + stores + extra


def accesses_im2col(spec: ConvSpec, out_width: int) -> float:
    """CMSIS-NN blocked path: per 2-column x 2-filter tile of the matmul,
    2K word loads produce 4K MACs (0.5 word/MAC) — the data-reuse engine the
    paper credits for the SIMD speedup. Patch construction costs
    K loads + K stores per output pixel. Add-conv has no SIMD path.
    """
    if spec.primitive == "add":
        return float(accesses_direct(spec, out_width))
    hy2 = out_width ** 2
    k = patch_len(spec)
    groups = spec.groups if spec.primitive == "grouped" else 1
    cy = spec.out_channels
    build = 0.0
    if spec.primitive in ("standard", "grouped", "shift"):
        build = 2.0 * k * hy2 * groups if spec.primitive == "grouped" else 2.0 * k * hy2
        # shift: construction gathers with per-channel offsets — same volume
    matmul_macs = hy2 * cy * k * (groups if spec.primitive == "grouped" else 1) / max(groups, 1)
    matmul_words = 0.5 * matmul_macs
    stores = hy2 * cy
    if spec.primitive == "dws":
        # depthwise stage stays scalar-ish (paper keeps NNoM dw), pointwise
        # needs no patch construction (K=Cx columns are the input rows).
        dw_spec = dataclasses.replace(spec, primitive="standard",
                                      in_channels=1, out_channels=1)
        dw = spec.in_channels * (2 * spec.kernel_size ** 2 * hy2 + hy2)
        return dw + matmul_words + stores
    return build + matmul_words + stores


def reuse_ratio(spec: ConvSpec, out_width: int) -> float:
    """Fig 3 quantity: (accesses without SIMD) / (accesses with SIMD), per MAC."""
    macs = spec.mac_count(out_width)
    return (accesses_direct(spec, out_width) / macs) / (accesses_im2col(spec, out_width) / macs)


# --------------------------------------------------------------------------
# MCU latency / power / energy model (STM32F401RE @ 3.3V)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MCUModel:
    # P(f) = p_static + p_per_mhz * f   — fit to paper Table 3
    p_static_mw: float = 11.0
    p_per_mhz_scalar: float = 0.513
    p_per_mhz_simd: float = 0.645
    # cycle model: scalar MAC ~ 5 cycles (ldr,ldr,mla,addr-arith); SMLAD does
    # 2 MACs/cycle with word loads amortized over the 2x2 tile.
    cycles_per_mac_scalar: float = 5.0
    cycles_per_mac_simd: float = 0.9
    cycles_per_access: float = 1.4       # paper: memory-access bound gaps
    o0_penalty_scalar: float = 1.52      # Table 4 optimization speedups
    o0_penalty_simd: float = 9.81

    def latency_s(self, spec: ConvSpec, out_width: int, *, simd: bool,
                  f_mhz: float = 84.0, opt: str = "Os") -> float:
        macs = spec.mac_count(out_width)
        if simd and spec.primitive != "add":
            cyc = (self.cycles_per_mac_simd * macs
                   + self.cycles_per_access * accesses_im2col(spec, out_width))
            if opt == "O0":
                cyc *= self.o0_penalty_simd
        else:
            cyc = (self.cycles_per_mac_scalar * macs
                   + self.cycles_per_access * accesses_direct(spec, out_width))
            if opt == "O0":
                cyc *= self.o0_penalty_scalar
        return cyc / (f_mhz * 1e6)

    def power_mw(self, *, simd: bool, f_mhz: float = 84.0) -> float:
        slope = self.p_per_mhz_simd if simd else self.p_per_mhz_scalar
        return self.p_static_mw + slope * f_mhz

    def energy_mj(self, spec: ConvSpec, out_width: int, *, simd: bool,
                  f_mhz: float = 84.0, opt: str = "Os") -> float:
        return self.power_mw(simd=simd, f_mhz=f_mhz) * self.latency_s(
            spec, out_width, simd=simd, f_mhz=f_mhz, opt=opt)


# --------------------------------------------------------------------------
# TPU v5e first-order hardware + energy constants (roofline terms)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TPUv5e:
    peak_bf16_flops: float = 197e12          # per chip
    hbm_bw: float = 819e9                    # B/s per chip
    ici_link_bw: float = 50e9                # B/s per link
    ici_links: int = 4                       # v5e 2D torus: 4 links/chip
    dcn_bw: float = 25e9                     # B/s per host pair (pod axis)
    # 128 MiB of VMEM per chip; Mosaic kernels get the 16 MiB default
    # scoped limit, which is the budget the footprint model enforces
    vmem_bytes: int = 16 * 2 ** 20
    hbm_bytes: int = 16 * 2 ** 30
    # order-of-magnitude energy terms (pJ) — used by the energy model only
    pj_per_flop: float = 0.35
    pj_per_hbm_byte: float = 6.0
    pj_per_ici_byte: float = 10.0
    static_w: float = 60.0

    def energy_j(self, flops: float, hbm_bytes: float, ici_bytes: float,
                 seconds: float) -> float:
        dyn = (flops * self.pj_per_flop + hbm_bytes * self.pj_per_hbm_byte
               + ici_bytes * self.pj_per_ici_byte) * 1e-12
        return dyn + self.static_w * seconds

    def roofline_terms(self, flops: float, hbm_bytes: float, ici_bytes: float):
        """Seconds spent in each bottleneck if perfectly overlapped."""
        return dict(
            compute_s=flops / self.peak_bf16_flops,
            memory_s=hbm_bytes / self.hbm_bw,
            collective_s=ici_bytes / (self.ici_links * self.ici_link_bw),
        )


#: ``device_kind`` strings JAX reports for a TPU v5e chip
V5E_KINDS = ("TPU v5 lite", "TPU v5e")


def target_chip() -> TPUv5e:
    """Constants of the chip the kernels are scheduled for (analytic peaks,
    VMEM budget). Off the TPU — the CPU, or Pallas interpret mode — the
    target is v5e on purpose: interpret mode validates v5e feasibility. On
    a TPU the attached chip must be a v5e; any other kind raises instead of
    being scheduled with v5e numbers."""
    import jax
    from repro.kernels.common import use_interpret
    if jax.default_backend() != "tpu" or use_interpret():
        return TPUv5e()
    kind = jax.devices()[0].device_kind
    if kind not in V5E_KINDS:
        raise RuntimeError(
            f"no hardware constants for TPU device_kind {kind!r}: the tuner's "
            f"peaks and the VMEM budget describe only {V5E_KINDS}")
    return TPUv5e()
