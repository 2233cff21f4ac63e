"""Hardware specifications and roofline math.

Two chips ship by default: TPU_V5E (the target platform for the TPU-native
characterisation and the multi-pod dry-run) and H200_SXM (used to validate the
energy/DVFS simulator against the paper's published numbers).
"""
from repro.hw.chips import (
    HardwareSpec,
    TPU_V5E,
    H200_SXM,
    DEVICE_KINDS,
    chip_for_device_kind,
    get_chip,
)
from repro.hw.roofline import (
    RooflineTerms,
    roofline_terms,
    ridge_point,
    arithmetic_intensity,
    bound_class,
)

__all__ = [
    "HardwareSpec",
    "TPU_V5E",
    "H200_SXM",
    "DEVICE_KINDS",
    "chip_for_device_kind",
    "get_chip",
    "RooflineTerms",
    "roofline_terms",
    "ridge_point",
    "arithmetic_intensity",
    "bound_class",
]
