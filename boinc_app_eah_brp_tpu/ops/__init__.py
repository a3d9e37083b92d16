from .harmonic import harmonic_sumspec, harmonic_sumspec_batch
from .pallas_resample import pallas_applicable
from .resample import resample, resample_batch, resample_split
from .sincos import sin_lut, sincos_lut_lookup
from .spectrum import (
    power_spectrum,
    power_spectrum_batch,
    power_spectrum_split,
)

__all__ = [
    "harmonic_sumspec",
    "harmonic_sumspec_batch",
    "pallas_applicable",
    "resample",
    "resample_batch",
    "resample_split",
    "sin_lut",
    "sincos_lut_lookup",
    "power_spectrum",
    "power_spectrum_batch",
    "power_spectrum_split",
]
