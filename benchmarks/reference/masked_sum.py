"""Plain reference: the threshold allreduce's answer, and the payload it is
asked about.

The payload is the benchmark's, not the program's: element ``i`` of device
``d`` in round ``r`` is an integer hash of ``(seed, r, d, i)`` cut to 16 bits
and scaled to a multiple of 2^-15 in [-1, 1). Integer arithmetic wraps the
same way in numpy and on the device, so the host can make any element again
without fetching it; and any order of float32 additions of up to 256 such
values is exact, so the right answer is one bit pattern: the comparison is
exact and a lower-precision wire cannot pass.

The answer (the configuration's guarantees): ``sum`` is the sum of exactly the
unmasked devices' payloads and ``count`` their number, in every element.

Every function takes ``xp``: ``numpy`` on the host, ``jax.numpy`` on a device.
"""

from __future__ import annotations

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D
_M4, _M5, _M6 = 0x27D4EB2F, 0x2C1B3C6D, 0x297A2D39


def _wrap(x: int) -> int:
    return x & 0xFFFFFFFF


def stream_offset(seed: int, round_: int, device: int) -> int:
    """The 32-bit word that separates one (seed, round, device) stream from
    another; plain Python integers, so nothing overflows."""
    return _wrap(
        _wrap(seed & 0xFFFFFFFF) * _M4 + _wrap(seed >> 32) * _M6
        + _wrap(round_ * _M2) + _wrap(device * _M3) + 0x165667B1
    )


def stream_offset_u32(seed: int, round_, device, xp):
    """:func:`stream_offset` where round and device are uint32 arrays (traced
    on the device); equal to it modulo 2^32."""
    const = _wrap(
        _wrap(seed & 0xFFFFFFFF) * _M4 + _wrap(seed >> 32) * _M6 + 0x165667B1
    )
    return round_ * np.uint32(_M2) + device * np.uint32(_M3) + np.uint32(const)


def payload(index, offset, xp=np):
    """float32 payload at ``index`` (uint32 array) of the stream ``offset``
    (a uint32 scalar: a Python int on the host, traced on the device)."""
    u = np.uint32  # numpy scalars on both sides: constants pass 2**31
    if isinstance(offset, int):
        offset = u(offset)
    with np.errstate(over="ignore"):
        h = index * u(_M1) + offset
        h = (h ^ (h >> u(15))) * u(_M5)
        h = (h ^ (h >> u(12))) * u(_M6)
        h = h ^ (h >> u(15))
    low = (h & u(0xFFFF)).astype(xp.int32) - np.int32(32768)
    return low.astype(xp.float32) * np.float32(2.0 ** -15)


def masked_sum(index, seed: int, round_: int, mask, xp=np):
    """``(sum, count)`` at ``index`` for one round: float32, the unmasked
    devices only. ``mask`` is a host sequence of 0/1, one entry a device."""
    total = xp.zeros(index.shape, xp.float32)
    for device, on in enumerate(mask):
        if on:
            total = total + payload(
                index, stream_offset(seed, round_, device), xp
            )
    return total, float(sum(1 for on in mask if on))
