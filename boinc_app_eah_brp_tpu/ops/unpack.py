"""Device-side 4-bit workunit unpack (H2D bandwidth optimization).

The reference unpacks the gzip payload on the host and works from the
float time series (``demod_binary.c:830-842``).  Here the scarce resource
is host-to-device bandwidth: the unpacked float32 parity halves of the
production WU are ~17 MB, the raw 4-bit payload is ~2.1 MB.  So the
driver ships the PACKED bytes and the device splits nibbles.

Bit-exactness: the host unpack divides the nibble by the header's double
``scale`` with one rounding to float32.  A float32 division on device
could round differently, so the 16 possible results are precomputed on
the host with the exact host arithmetic (``nibble_lut``) and the device
only gathers from that table — identical bytes out by construction
(``tests/test_packed_upload.py``).

The nibble order is the parity split: byte ``b`` yields even sample
``b >> 4`` and odd sample ``b & 15`` (``io/workunit.py::unpack_4bit``),
exactly the ``(even, odd)`` halves the packed FFT path uploads
(``ops/whiten.py``) — no device-side deinterleave is needed at all.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..runtime.devicecost import stage_scope


def nibble_lut(scale: float) -> np.ndarray:
    """float32[16]: ``lut[v] = float32(float64(v) / float64(scale))`` —
    the host unpack's exact value for each possible nibble."""
    scale64 = np.float64(scale)
    return (np.arange(16, dtype=np.float64) / scale64).astype(np.float32)


def unpack_4bit_split_device(raw, lut):
    """(even, odd) float32 halves from packed nibble bytes, on device.

    ``raw``: uint8[n/2] device array (the gzip payload, already resident);
    ``lut``: float32[16] from :func:`nibble_lut`.  Jit-safe; the gather is
    a 16-entry table lookup the compiler lowers to vector selects.
    """
    with stage_scope("unpack"):
        raw = raw.astype(jnp.int32)  # uint8 shifts are fine but int32 gathers best
        even = jnp.take(lut, raw >> 4)
        odd = jnp.take(lut, raw & 0x0F)
        return even, odd
