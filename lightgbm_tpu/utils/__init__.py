"""Shared small utilities (reference: include/LightGBM/utils/common.h)."""


def round_up(x: int, m: int) -> int:
    """Smallest multiple of `m` that is >= `x`."""
    return (x + m - 1) // m * m


def kernel_name(family: str, **variant) -> str:
    """A Pallas kernel's name in a device trace: ``lgbm_<family>`` plus
    the static values that make a separately compiled variant, e.g.
    ``lgbm_wave_pass_k8_b64`` (a true flag adds its letter, a false one
    nothing). Stable across refactors of the kernel body's name."""
    parts = ["lgbm_" + family]
    for key, value in variant.items():
        if isinstance(value, bool):
            if value:
                parts.append(key)
        else:
            parts.append(f"{key}{int(value)}")
    return "_".join(parts)
