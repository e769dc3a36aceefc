"""Paper Table 1: analytic parameters / MACs per primitive, verified against
the instantiated layers."""
from __future__ import annotations

import jax
import numpy as np

from repro.core import ConvSpec, init

from .common import emit


def main():
    hy = 32
    for prim in ("standard", "grouped", "dws", "shift", "add"):
        spec = ConvSpec(primitive=prim, in_channels=16, out_channels=16,
                        kernel_size=3, groups=2 if prim == "grouped" else 1,
                        use_bias=False)
        p = init(jax.random.PRNGKey(0), spec)
        actual = sum(int(np.prod(v.shape)) for k, v in p.items()
                     if k != "shifts")
        if prim == "shift":
            actual += int(np.prod(p["shifts"].shape))
        emit(f"table1/{prim}", 0.0,
             f"params={spec.param_count()} actual={actual} "
             f"macs={spec.mac_count(hy)} "
             f"param_gain={spec.param_count()/ConvSpec(in_channels=16, out_channels=16).param_count():.3f} "
             f"mac_gain={spec.mac_count(hy)/ConvSpec(in_channels=16, out_channels=16).mac_count(hy):.3f}")
        assert actual == spec.param_count(), (prim, actual, spec.param_count())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
