"""Hand-written Pallas TPU kernels for hot inner loops.

Reference parity: the reference's hottest single-purpose device kernels
live in spark-rapids-jni (Hash, CastStrings, ...) below the general cudf
algebra. Same layering here: XLA owns fusion for general expressions;
these Pallas kernels take over specific bandwidth-bound inner loops where
a hand-tiled VMEM pipeline beats the XLA default:

- murmur3_int32: the per-row hash behind every hash exchange, shuffled
  join, and group-key normalization. Elementwise uint32 rotate/multiply
  chains — one VMEM-resident pass, no intermediate HBM traffic.
- ascii_case_map: upper/lower over string BYTE planes (uint8), the inner
  loop of Upper/Lower over flat vocab/byte planes.

Both kernels carry a lax/XLA twin in ops/kernels.py; the conf
spark.rapids.sql.pallas.enabled picks the implementation, and the suite
runs the Pallas path in interpret mode on CPU so correctness is always
differentially checked against the XLA twin without hardware.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_BLOCK = 1024  # rows per grid step: 8 sublanes x 128 lanes

_ENABLED = True
_APPLIED = False


def set_enabled(v: bool) -> None:
    """spark.rapids.sql.pallas.enabled. PROCESS-GLOBAL and effectively
    startup-only: fused kernels cache compiled closures process-wide, so
    the first session's value wins; later sessions asking for a different
    value get a warning, not a silent partial flip."""
    global _ENABLED, _APPLIED
    v = bool(v)
    if _APPLIED and v != _ENABLED:
        import warnings
        warnings.warn(
            "spark.rapids.sql.pallas.enabled differs from the value the "
            "process started with; kernel caches are process-global, so "
            "the first value stays in effect", stacklevel=2)
        return
    _ENABLED = v
    _APPLIED = True


def enabled() -> bool:
    return _ENABLED


def _interpret() -> bool:
    """Every pallas_call site's interpret flag: Mosaic on the chip, the
    Pallas interpreter on the CPU simulator (the suite's differential
    check). A chip run asserts this is False (chip_smoke.py)."""
    return jax.default_backend() != "tpu"


def _x64_off():
    """Context manager tracing in 32-bit mode."""
    return jax.enable_x64(False)


def pallas_supported(n: int) -> bool:
    """Pallas path eligibility: block-aligned plane sizes only (the
    capacity bucketing makes every plane >= 1024 a multiple of 1024)."""
    return n >= _BLOCK and n % _BLOCK == 0


_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _mm3_kernel(seed_ref, x_ref, o_ref):
    x = x_ref[...]  # already uint32 (a no-op convert here trips Mosaic)
    seed = seed_ref[0]
    k1 = x * _C1
    k1 = (k1 << 15) | (k1 >> 17)
    k1 = k1 * _C2
    h1 = seed ^ k1
    h1 = (h1 << 13) | (h1 >> 19)
    h1 = h1 * np.uint32(5) + np.uint32(0xE6546B64)
    # fmix(h1 ^ len), len = 4
    h1 = h1 ^ np.uint32(4)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * np.uint32(0xC2B2AE35)
    o_ref[...] = h1 ^ (h1 >> 16)


def murmur3_int32_pallas(values: jax.Array, seed: jax.Array) -> jax.Array:
    """Spark murmur3 of an int32 plane (hashInt), Pallas-tiled. `seed`
    must be a SCALAR riding in SMEM (per-row seed planes — chained
    multi-column hashing — stay on the lax twin: Mosaic on this toolchain
    miscompiles the two-VMEM-input variant of this op chain)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = values.shape[0]
    assert pallas_supported(n) and seed.ndim == 0, (n, seed.shape)
    x = values.astype(jnp.uint32).reshape(n // 128, 128)
    rows = x.shape[0]
    block_rows = _BLOCK // 128
    seed_arr = jnp.reshape(seed.astype(jnp.uint32), (1,))
    # the engine runs with global x64 enabled, under which pallas grid
    # index types lower to i64 and Mosaic fails to legalize; this kernel
    # is all-32-bit, so trace it in 32-bit mode
    with _x64_off():
        out = pl.pallas_call(
            _mm3_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
            grid=(rows // block_rows,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            interpret=_interpret(),
        )(seed_arr, x)
    return out.reshape(n)


def _swar_case_kernel(lo_b, hi_b, delta_sign):
    """SWAR ASCII case map over u32 words (4 bytes/lane): per-byte range
    test with carry-safe 7-bit arithmetic, then +-32 on selected bytes.
    Mosaic on this toolchain does not lower u8 lanes; 4-bytes-per-u32
    also quarters the lane count."""
    HI = np.uint32(0x80808080)
    LO7 = np.uint32(0x7F7F7F7F)
    ge = np.uint32(0x01010101) * np.uint32(0x80 - lo_b)
    gt = np.uint32(0x01010101) * np.uint32(0x80 - (hi_b + 1))

    def kern(x_ref, o_ref):
        x = x_ref[...]
        hi = x & HI
        lo = x & LO7
        is_ge = (lo + ge) & HI          # byte >= lo_b (7-bit range)
        is_gt = (lo + gt) & HI          # byte > hi_b
        mask = is_ge & ~is_gt & ~hi     # ASCII and in [lo_b, hi_b]
        delta = (mask >> 2)             # 0x80 -> 0x20 (= 32) per byte
        o_ref[...] = (x - delta) if delta_sign < 0 else (x + delta)

    return kern


def ascii_case_map_pallas(raw: jax.Array, upper: bool) -> jax.Array:
    """ASCII case map over a uint8 byte plane (byte planes are
    capacity-bucketed, so multiples of 4096 take this path)."""
    from jax import lax
    from jax.experimental import pallas as pl
    n = raw.shape[0]
    assert n % 4096 == 0, n
    with _x64_off():  # see murmur3_int32_pallas
        words = lax.bitcast_convert_type(raw.reshape(n // 4, 4), jnp.uint32)
        x = words.reshape(n // 4 // 128, 128)
        rows = x.shape[0]
        block_rows = 8
        kern = (_swar_case_kernel(97, 122, -1) if upper
                else _swar_case_kernel(65, 90, +1))
        out = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
            grid=(rows // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            interpret=_interpret(),
        )(x)
        return lax.bitcast_convert_type(
            out.reshape(n // 4), jnp.uint8).reshape(n)
