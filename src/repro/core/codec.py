"""Profile-word precision control — the ``ap_fixed<W,I>`` sweep (paper Fig. 4).

The paper stores profile words as ``ap_fixed<W,I>`` and sweeps W to trade
resource overhead against overflow risk: with max observed FIFO depth 66,
bitwidths below ~6 overflow.  On TPU the analogue is the record dtype of the
tape/stream buffer (f32 / bf16 / f16 / f8) plus an emulated fixed-point codec
for integer-valued metrics, which reproduces the paper's overflow cliff
exactly (saturating quantization).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

# dtypes usable directly as the stream/tape buffer element type.
FLOAT_FORMATS = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float8_e4m3": jnp.float8_e4m3fn,
}


@dataclasses.dataclass(frozen=True)
class FixedPointCodec:
    """Saturating signed fixed-point ``ap_fixed<total_bits, int_bits>``.

    ``encode`` quantizes to the grid and saturates; ``decode`` returns the
    dequantized float.  ``total_bits == int_bits`` gives the paper's pure
    integer profile words.  Storage container is chosen from total_bits so
    the *bytes moved* by the profile path scale the way the paper's BRAM/FF
    cost does.
    """

    total_bits: int
    int_bits: Optional[int] = None  # defaults to total_bits (pure integer)

    def __post_init__(self):
        if not (2 <= self.total_bits <= 32):
            raise ValueError("total_bits must be in [2, 32]")
        ib = self.total_bits if self.int_bits is None else self.int_bits
        if ib > self.total_bits:
            raise ValueError("int_bits cannot exceed total_bits")

    @property
    def _int_bits(self) -> int:
        return self.total_bits if self.int_bits is None else self.int_bits

    @property
    def frac_bits(self) -> int:
        return self.total_bits - self._int_bits

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def max_value(self) -> float:
        return (2 ** (self.total_bits - 1) - 1) / self.scale

    @property
    def min_value(self) -> float:
        return -(2 ** (self.total_bits - 1)) / self.scale

    @property
    def storage_dtype(self):
        if self.total_bits <= 8:
            return jnp.int8
        if self.total_bits <= 16:
            return jnp.int16
        return jnp.int32

    @property
    def storage_bytes_per_word(self) -> int:
        return jnp.dtype(self.storage_dtype).itemsize

    def encode(self, x: jnp.ndarray) -> jnp.ndarray:
        q = jnp.round(jnp.asarray(x, jnp.float32) * self.scale)
        q = jnp.clip(q, -(2 ** (self.total_bits - 1)), 2 ** (self.total_bits - 1) - 1)
        return q.astype(self.storage_dtype)

    def decode(self, q: jnp.ndarray) -> jnp.ndarray:
        return q.astype(jnp.float32) / self.scale

    def roundtrip(self, x: jnp.ndarray) -> jnp.ndarray:
        """Quantize-dequantize; saturation makes overflow observable."""
        return self.decode(self.encode(x))

    def overflows(self, x) -> jnp.ndarray:
        """True where the value cannot be represented (paper's Fig. 4 cliff)."""
        x = jnp.asarray(x, jnp.float32)
        return (x > self.max_value) | (x < self.min_value)


# --------------------------------------------------------------------- #
# profile-word integrity checksum
# --------------------------------------------------------------------- #
CHECKSUM_BITS = 24  # integers < 2**24 survive a float32 word exactly


def word_checksum(values: jnp.ndarray) -> jnp.ndarray:
    """XOR-fold checksum of profile words, exact through a float32 stream.

    Folds the float32 bit patterns of ``values`` into one integer below
    ``2**CHECKSUM_BITS`` so the checksum itself can ride the stream as an
    ordinary profile word with zero quantization loss.  Any single bit flip
    in payload or checksum word changes the fold, so host-side verification
    catches it.  Pure jnp — safe under jit.
    """
    v = jnp.atleast_1d(jnp.asarray(values)).reshape(-1).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    # mix position in so swapped words are detected too
    pos = (jnp.arange(bits.shape[0], dtype=jnp.uint32) + jnp.uint32(1))
    bits = bits ^ (pos * jnp.uint32(0x9E3779B1))
    folded = jax.lax.reduce(bits, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
    folded = (folded ^ (folded >> CHECKSUM_BITS)) & jnp.uint32(
        (1 << CHECKSUM_BITS) - 1)
    return folded.astype(jnp.float32)


def verify_checksum(values, checksum_word) -> bool:
    """Host-side re-computation; True when the payload is intact."""
    import numpy as np

    expect = float(np.asarray(jax.device_get(word_checksum(values))))
    return float(checksum_word) == expect


# --------------------------------------------------------------------- #
# CRC-32 guard mode (optional; stronger than the default 24-bit XOR fold)
# --------------------------------------------------------------------- #
_CRC32_POLY = 0xEDB88320  # IEEE 802.3, reflected
_CRC32_TABLE = None


def _crc32_table() -> jnp.ndarray:
    """The 256-entry byte-at-a-time CRC-32 table (built once, host-side).

    The cache holds the NumPy table: a ``jnp`` array made while a jitted
    caller traces is that trace's tracer, and would leak into the next."""
    global _CRC32_TABLE
    if _CRC32_TABLE is None:
        import numpy as np

        t = np.arange(256, dtype=np.uint32)
        for _ in range(8):
            t = np.where(t & 1, (t >> 1) ^ np.uint32(_CRC32_POLY), t >> 1)
        _CRC32_TABLE = t
    return jnp.asarray(_CRC32_TABLE)


def word_crc32(values: jnp.ndarray) -> jnp.ndarray:
    """CRC-32 of the payload's float32 byte stream, as two stream words.

    Computes the standard CRC-32 (``binascii.crc32``) over the
    little-endian bytes of the float32 bit patterns, table-driven under
    ``lax.scan`` so it stays jit-safe.  The 32-bit digest is returned as
    ``[lo16, hi16]`` — each half is below ``2**16``, so both ride a
    float32 stream with zero quantization loss.  Where the XOR fold only
    guarantees detection of single-bit flips, the CRC detects all burst
    errors up to 32 bits — the guard a DMA-corrupted transfer needs.
    """
    v = jnp.atleast_1d(jnp.asarray(values)).reshape(-1).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    lanes = [(bits >> (8 * k)) & jnp.uint32(0xFF) for k in range(4)]
    stream = jnp.stack(lanes, axis=1).reshape(-1)
    table = _crc32_table()

    def step(crc, b):
        return table[(crc ^ b) & jnp.uint32(0xFF)] ^ (crc >> 8), None

    crc, _ = jax.lax.scan(step, jnp.uint32(0xFFFFFFFF), stream)
    crc = crc ^ jnp.uint32(0xFFFFFFFF)
    lo = (crc & jnp.uint32(0xFFFF)).astype(jnp.float32)
    hi = (crc >> 16).astype(jnp.float32)
    return jnp.stack([lo, hi])


def verify_crc32(values, guard_words) -> bool:
    """Host-side CRC re-computation; True when the payload is intact."""
    import numpy as np

    expect = np.asarray(jax.device_get(word_crc32(values)), dtype=np.float64)
    got = np.asarray(guard_words, dtype=np.float64).reshape(-1)
    return (got.shape[0] == 2 and float(got[0]) == float(expect[0])
            and float(got[1]) == float(expect[1]))
