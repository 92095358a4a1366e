"""``cos`` for arguments of bounded size, in plain ``lax``.

XLA's ``cos`` answers for every float32 — its argument reduction holds to
3·10³⁸ and every element pays for it: on a TPU v5e it adds 6.1 ms to a
product of 65,536 × 440 × 4,096 that takes 1.7 with its write (``PERF.md``
§6, PR 38). An argument known to lie within :data:`LIMIT` needs 20 vector
operations: ``cos z = (−1)ᵏ sin((k + ½)π − z)`` with ``k = ⌊z/π⌋``, a
two-constant Cody–Waite reduction, ONE odd minimax polynomial on
[−π/2, π/2] and the sign put in by its bit — all float32 and elementwise,
so XLA fuses the body behind a product as it fuses ``jnp.cos``.

Beyond :data:`LIMIT` the reduction's products stop being exact and the
body is WRONG (``nan`` and ``inf`` give garbage): a caller proves the
range first (``CosineRandomFeatures`` does, per call, on the device).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

#: |z| up to which :func:`cos_bounded` holds its error: k + ½ then has 12
#: bits and its product with the 12-bit head of π is exact in float32
LIMIT = 4096.0

#: the largest |cos_bounded(z) − cos(z)| over |z| ≤ LIMIT the tests allow.
#: Read over EVERY float32 in range (numpy's float32 arithmetic): 1.282·10⁻⁷,
#: at |r| near π/2 where r itself carries half an ulp of 1.5; XLA's own
#: float32 ``cos`` reads 1.3·10⁻⁷ on a TPU v5e and 3.3·10⁻⁸ on the CPU
MAX_ABS_ERROR = 1.5e-7

_F = np.float32
_INV_PI = _F(1.0 / np.pi)
#: π = 3217/1024 − 8.9089…·10⁻⁶: a head of 12 bits, the rest in float32
_PI_HI, _PI_LO = _F(3.1416015625), _F(-8.90890987648163e-06)
#: sin r ≈ r + r³·S(r²) on [−1.5716, 1.5716], minimax to 4.6·10⁻⁹
#: (highest power first)
_SIN = (
    _F(2.5998992896347772e-06), _F(-0.00019806544878520072),
    _F(0.008333016186952591), _F(-0.16666656732559204),
)


def cos_bounded(z):
    """``cos(z)`` for float32 ``z`` with ``|z| <= LIMIT``, to
    :data:`MAX_ABS_ERROR` of the float64 cosine. Written in ``lax``
    primitives: as many ``jnp`` calls cost a trace several times as long."""
    mul, add, sub = lax.mul, lax.add, lax.sub
    k = lax.floor(mul(z, _INV_PI))
    half = add(k, _F(0.5))
    # r = (k + ½)π − z lies in [−π/2, π/2]; the first product is exact
    r = add(sub(mul(half, _PI_HI), z), mul(half, _PI_LO))
    r2 = mul(r, r)
    p = add(mul(r2, _SIN[0]), _SIN[1])
    for c in _SIN[2:]:
        p = add(mul(p, r2), c)
    sin = add(r, mul(r, mul(r2, p)))
    # (−1)ᵏ: k's lowest bit moved onto the sign
    odd = lax.shift_left(lax.convert_element_type(k, jnp.int32), np.int32(31))
    bits = lax.bitcast_convert_type(sin, jnp.int32)
    return lax.bitcast_convert_type(lax.bitwise_xor(bits, odd), jnp.float32)
