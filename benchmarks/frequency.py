"""Paper Table 3 / Fig 4: MCU frequency sweep. P(f) = P_static + k*f is
calibrated to the paper's measured mW; the model reproduces the paper's
conclusion that max frequency minimizes energy per inference."""
from __future__ import annotations

from repro.core import ConvSpec, MCUModel

from .common import emit


def main():
    mcu = MCUModel()
    # paper §4.2 fixed layer: groups 2, k3, width 32, cin 3, cout 32
    spec = ConvSpec(primitive="standard", in_channels=3, out_channels=32,
                    kernel_size=3, use_bias=False)
    for simd in (False, True):
        tag = "simd" if simd else "no_simd"
        energies = []
        for f in (10, 20, 40, 80):
            p = mcu.power_mw(simd=simd, f_mhz=f)
            lat = mcu.latency_s(spec, 32, simd=simd, f_mhz=f)
            e = mcu.energy_mj(spec, 32, simd=simd, f_mhz=f)
            energies.append(e)
            emit(f"table3/{tag}/f={f}MHz", lat * 1e6,
                 f"power_mW={p:.2f} energy_mJ={e:.3f}")
        emit(f"table3/{tag}/claim_max_freq_lowest_energy", 0.0,
             f"{energies[-1] == min(energies)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
