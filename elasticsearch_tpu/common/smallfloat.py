"""Lucene SmallFloat byte315 codec — exact re-implementation.

Lucene 4.7 stores per-document field-length norms as ONE BYTE via
SmallFloat.floatToByte315 (3 mantissa bits, 5 exponent bits, exponent zero-point 15).
Both DefaultSimilarity (TF-IDF) and BM25Similarity encode norms through this codec, so
reproducing Lucene's exact hit ordering REQUIRES quantizing norms identically
(see SURVEY.md §7 "Hard parts": 1-byte norm quantization).

Reference behavior: org.apache.lucene.util.SmallFloat (Lucene 4.7.0 jar in
/root/reference/pom.xml:33); consumed by DefaultSimilarity.encodeNormValue and
BM25Similarity.encodeNormValue.

Implemented here from the IEEE-754 definition (float bits >> 21, rebased exponent), not
translated code: vectorized over numpy arrays for whole-segment encoding.
"""

from __future__ import annotations

import numpy as np


def float_to_byte315(f: np.ndarray | float) -> np.ndarray:
    """Encode float32 → uint8 with 3 mantissa bits / 5 exponent bits / zero-exp 15."""
    arr = np.atleast_1d(np.asarray(f, dtype=np.float32))
    bits = arr.view(np.int32)
    small = bits >> 21  # 24-3 mantissa shift
    floor = (63 - 15) << 3
    out = np.empty(arr.shape, dtype=np.uint8)
    too_small = small <= floor
    too_large = small >= floor + 0x100
    mid = ~(too_small | too_large)
    # underflow → 0 for non-positive, 1 for tiny positives (matches reference semantics)
    out[too_small] = np.where(bits[too_small] <= 0, 0, 1).astype(np.uint8)
    out[too_large] = 255
    out[mid] = (small[mid] - floor).astype(np.uint8)
    return out


def byte315_to_float(b: np.ndarray | int) -> np.ndarray:
    """Decode uint8 → float32. byte315_to_float(float_to_byte315(x)) quantizes x."""
    barr = np.atleast_1d(np.asarray(b, dtype=np.uint8))
    bits = (barr.astype(np.int32) << 21) + (((63 - 15) << 24))
    out = bits.view(np.float32).copy()
    out[barr == 0] = 0.0
    return out


# Precomputed 256-entry decode table — same trick as Lucene's NORM_TABLE caches.
NORM_TABLE: np.ndarray = byte315_to_float(np.arange(256, dtype=np.uint8))


def encode_norm(num_terms: np.ndarray | int, boost: float = 1.0) -> np.ndarray:
    """Norm byte for a document field with `num_terms` tokens:
    encode(boost / sqrt(numTerms)). Shared by TF-IDF and BM25 similarities."""
    n = np.maximum(np.atleast_1d(np.asarray(num_terms, dtype=np.float64)), 0)
    with np.errstate(divide="ignore"):
        f = np.where(n > 0, boost / np.sqrt(n), 0.0).astype(np.float32)
    return float_to_byte315(f)


def jnp_norm_table():
    """Device-side byte315 decode table: jnp float32 [256], the device twin of
    NORM_TABLE. Built fresh per call (it is a 1 KB constant — callers that trace
    it into a jitted program get it folded as a compile-time constant; eager
    callers pay one explicit 1 KB upload). Kept out of module import so merely
    importing the codec never touches a device."""
    import jax.numpy as jnp

    from .jaxenv import compile_tag

    # compile_tag: eager table uploads are codec/packing work — outermost
    # scope wins, so a traced caller (the mesh program) keeps its own family.
    with compile_tag("pack"):
        return jnp.asarray(NORM_TABLE.astype(np.float32))


def jnp_byte315_to_float(b):
    """Device byte315 decode: uint8/int array → float32 via the 256-entry
    table gather, bitwise-identical to host byte315_to_float. The reference
    form of the decode the kernels inline themselves — the sparse scan gathers
    jnp_norm_table-derived SimTables rows, the mesh program uses
    jnp_norm_table directly — pinned against the host codec by
    tests/test_quantized_postings.py. jnp.take, not fancy indexing: this may
    run eagerly, where fancy indexing routes a scalar through an implicit
    transfer the sanitizer rejects."""
    import jax.numpy as jnp

    from .jaxenv import compile_tag

    with compile_tag("pack"):
        return jnp.take(jnp_norm_table(), jnp.asarray(b).astype(jnp.int32))


def decode_norm_doclen(norm_byte: np.ndarray) -> np.ndarray:
    """BM25: decoded value f represents boost/sqrt(len); doc length = 1/f² (quantized).
    Bytes decoding to 0 (empty field) get length 0."""
    f = NORM_TABLE[np.asarray(norm_byte, dtype=np.uint8)]
    with np.errstate(divide="ignore"):
        dl = np.where(f > 0, 1.0 / (f * f), 0.0)
    return dl.astype(np.float32)
