"""Number formats and round-to-nearest quantizers (paper §A.4).

All quantizers are pure jnp, jit-safe, dtype-preserving "fake quant":
they return values snapped to the target format's representable grid.
Formats implemented:

* ``IntFormat(n)``      — n-bit symmetric signed integer grid (±(2^(n-1)-1)).
* ``FloatFormat(e, m)`` — EeMm minifloat with subnormals; E4M3 uses the OCP
  448 max (top mantissa code reserved), others use the full grid.
* ``E8M0``              — power-of-two-only scale format (MX block scales).

The per-tensor max-scaling scheme of Eqs. (13)/(14) is provided by
``quantize_tensor_scaled``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class IntFormat:
    """n-bit symmetric 2's-complement-style integer grid."""

    bits: int

    @property
    def max_val(self) -> float:
        return float(2 ** (self.bits - 1) - 1)

    @property
    def name(self) -> str:
        return f"INT{self.bits}"

    def quantize(self, x: jax.Array) -> jax.Array:
        m = self.max_val
        return jnp.clip(jnp.round(x), -m, m)

    def levels(self) -> np.ndarray:
        m = int(self.max_val)
        return np.arange(-m, m + 1, dtype=np.float32)


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """EeMm minifloat, round-to-nearest-even on the mantissa, saturating.

    ``bias`` defaults to 2^(e-1)-1.  ``ocp_e4m3`` reserves the top mantissa
    code at the top exponent (max 448) as in the OCP FP8 spec, which is the
    E4M3 the paper uses for block-array scale factors.
    """

    exp_bits: int
    man_bits: int
    bias: int | None = None
    ocp_e4m3: bool = False

    @property
    def name(self) -> str:
        return f"E{self.exp_bits}M{self.man_bits}"

    @property
    def _bias(self) -> int:
        if self.bias is not None:
            return self.bias
        return 2 ** (self.exp_bits - 1) - 1

    @property
    def max_val(self) -> float:
        emax = (2**self.exp_bits - 1) - self._bias
        if self.ocp_e4m3:
            # OCP FP8 E4M3: mantissa all-ones at the top exponent is NaN,
            # so the max magnitude is 2^8 * 1.75 = 448.
            return float(2.0**emax * (2.0 - 2.0 ** (1 - self.man_bits)))
        return float(2.0**emax * (2.0 - 2.0 ** (-self.man_bits)))

    @property
    def min_subnormal(self) -> float:
        return float(2.0 ** (1 - self._bias) * 2.0 ** (-self.man_bits))

    def quantize(self, x: jax.Array) -> jax.Array:
        dt = x.dtype
        x = x.astype(jnp.float32)
        sign = jnp.sign(x)
        a = jnp.abs(x)
        # exponent of the containing binade, clamped to subnormal floor
        e = jnp.floor(jnp.log2(jnp.maximum(a, 1e-38)))
        e = jnp.clip(e, 1 - self._bias, (2**self.exp_bits - 1) - self._bias)
        ulp = 2.0**e * 2.0 ** (-self.man_bits)
        q = jnp.round(a / ulp) * ulp
        # rounding can carry into the next binade; that value is exactly
        # representable there, so no correction is needed beyond clamping.
        q = jnp.minimum(q, self.max_val)
        q = jnp.where(a == 0.0, 0.0, q)
        return (sign * q).astype(dt)

    def levels(self) -> np.ndarray:
        """All non-negative representable values (for tests / codebook plots)."""
        vals = {0.0}
        for code_e in range(2**self.exp_bits):
            for code_m in range(2**self.man_bits):
                if code_e == 0:  # subnormal
                    v = 2.0 ** (1 - self._bias) * (code_m * 2.0 ** (-self.man_bits))
                else:
                    v = 2.0 ** (code_e - self._bias) * (1.0 + code_m * 2.0 ** (-self.man_bits))
                if v <= self.max_val + 1e-12:
                    vals.add(v)
        return np.array(sorted(vals), dtype=np.float32)


@dataclasses.dataclass(frozen=True)
class E8M0Format:
    """Power-of-two scale format used by MX: value = 2^k, k in [-127, 127]."""

    @property
    def name(self) -> str:
        return "E8M0"

    @property
    def max_val(self) -> float:
        return float(2.0**127)

    def quantize(self, x: jax.Array) -> jax.Array:
        dt = x.dtype
        a = jnp.abs(x.astype(jnp.float32))
        k = jnp.round(jnp.log2(jnp.maximum(a, 1e-38)))
        k = jnp.clip(k, -127, 127)
        q = jnp.where(a == 0.0, 0.0, 2.0**k)
        return (jnp.sign(x) * q).astype(dt)


# --- canonical instances -------------------------------------------------
INT4 = IntFormat(4)
INT6 = IntFormat(6)
INT8 = IntFormat(8)


E4M3 = FloatFormat(4, 3, ocp_e4m3=True)  # OCP FP8: max 448
E5M2 = FloatFormat(5, 2)
E2M1 = FloatFormat(2, 1)  # MXFP4 element format, max 6.0
E1M2 = FloatFormat(1, 2)  # paper's proxy for MX4, max 3.5
E3M0 = FloatFormat(3, 0)
E8M0 = E8M0Format()

FORMATS = {
    f.name: f
    for f in [INT4, INT6, INT8, E4M3, E5M2, E2M1, E1M2, E3M0, FloatFormat(3, 2), FloatFormat(3, 3)]
}
FORMATS["E8M0"] = E8M0


def quantize_tensor_scaled(x: jax.Array, fmt, axis=None) -> jax.Array:
    """Dynamic max-scaled quantization (Eqs. 13/14).

    ``axis=None`` → per-tensor scale; otherwise the scale is reduced over
    ``axis`` (kept-dims), giving per-row / per-block granularity.
    """
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = amax / fmt.max_val
    s = jnp.where(s == 0.0, 1.0, s)
    return (fmt.quantize(x / s) * s).astype(x.dtype)


@partial(jax.jit, static_argnames=("bits",))
def e4m3_to_bits(x: jax.Array, bits: int = 8) -> jax.Array:
    """Encode E4M3-grid-snapped positive scales to their uint8 bit pattern.

    Used by the packed path so scale storage is literally 8 bits.
    Input must be non-negative and already on the E4M3 grid.
    """
    del bits
    a = jnp.abs(x.astype(jnp.float32))
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 1e-38))), -6, 8)
    frac = a / 2.0**e  # in [1, 2) for normals
    is_sub = a < 2.0**-6
    man = jnp.where(is_sub, jnp.round(a / (2.0**-6 * 0.125)), jnp.round((frac - 1.0) * 8))
    code_e = jnp.where(is_sub, 0, e + 7).astype(jnp.uint8)
    man = jnp.clip(man, 0, 7).astype(jnp.uint8)
    return (code_e * 8 + man).astype(jnp.uint8)


def pow2(e: jax.Array) -> jax.Array:
    """Exact f32 ``2**e`` for integer ``e`` in the normal range, built from
    the exponent bits — no transcendental, so a Pallas kernel on the chip
    and XLA agree bit for bit."""
    return jax.lax.bitcast_convert_type((e.astype(jnp.int32) + 127) << 23, jnp.float32)


def bits_to_e4m3_impl(code: jax.Array) -> jax.Array:
    """Inverse of :func:`e4m3_to_bits` (positive scales only).  Un-jitted so
    it can be inlined inside Pallas kernel bodies."""
    code = code.astype(jnp.int32)
    code_e = code // 8
    man = (code % 8).astype(jnp.float32)
    sub = 2.0**-6 * (man * 0.125)
    nrm = pow2(code_e - 7) * (1.0 + man * 0.125)
    return jnp.where(code_e == 0, sub, nrm)


bits_to_e4m3 = jax.jit(bits_to_e4m3_impl)
