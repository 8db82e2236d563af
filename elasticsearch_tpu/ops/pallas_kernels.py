"""Pallas TPU kernels for the sparse scoring path.

`sparse_score` is the fully-fused form of the quantized sparse scan
(ops/scoring.py `_sparse_impl`): mask → BM25/TF-IDF → partial top-k in ONE pass
over the CSR block tiles. Per grid step (query q, block-slot t) the
scalar-prefetched `qblk` row indices select which [1, B] postings block rows
stream HBM→VMEM (Pallas double-buffers the DMAs across grid steps — the gather
the composed path lowers as a generic XLA gather becomes streaming DMA), the
prefetched `qfid` selects the clause field's 256-entry similarity LUT row, and
the same step then

  1. widens the quantized tf (uint8/int16 plane; f32 escape rides through),
  2. decodes the per-posting norm byte through the LUT (tf→tfn inside the
     scan — the byte315 encoding survives into the kernel, no baked f32 plane),
  3. applies the clause weight / const-clause select,
  4. folds the packed should/must/must_not counters,
  5. appends (doc, contrib, counter) into a per-query VMEM candidate
     accumulator that lives across the query's TB grid steps.

At the query's LAST block step the accumulator — still in VMEM — runs the
shared reduction (`scoring.sparse_reduce`: sort-by-doc, segment-sum duplicate
merge, bool semantics, `lax.top_k`) and writes only the [k] winners. The full
`[Qb, TB·128]` candidate matrix therefore never round-trips through HBM; HBM
traffic is one streaming read of the touched postings (6 B/posting quantized)
plus [Qb, k] results.

Opt-in: ESTPU_PALLAS=1 asks for the COMPILED kernel and nothing else — off a
TPU, or where the compiler refuses it, the launch raises; it never interprets
quietly (pending on-silicon benchmarking before any default flips).
ESTPU_PALLAS=interpret is the one way to interpret it, on any backend —
bitwise-identical semantics BY CONSTRUCTION (the final phase executes the same
sparse_reduce the composed path runs), which is how the parity suite exercises
it on the CPU test mesh; interpret mode is orders of magnitude slower.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from .device_index import BLOCK, TFN_BM25


def estpu_pallas_enabled() -> bool:
    """ESTPU_PALLAS=1 → the compiled kernel (raises off a TPU: interpret-mode
    Pallas on the serving path would be a silent orders-of-magnitude
    regression); ESTPU_PALLAS=interpret → the interpreted kernel, anywhere
    (tests/dev); anything else → off."""
    flag = os.environ.get("ESTPU_PALLAS", "0")
    if flag == "interpret":
        return True
    if flag != "1":
        return False
    _require_tpu()
    return True


def _require_tpu() -> None:
    """The compiled kernel needs a TPU. A backend that fails to initialise
    raises from jax.devices() itself; any other platform raises here."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            "ESTPU_PALLAS=1 asks for the compiled Pallas kernel, which needs a "
            f"TPU (jax reports {platform!r}); ESTPU_PALLAS=interpret is the only "
            "way to interpret it")


def _sparse_score_kernel(qblk_s, qfid_s, qmode_s, n_must_s, msm_s,  # SMEM prefetch
                         docs_ref, tf_ref, nb_ref, cache_ref,  # [1, B]/[1, 256] rows
                         qw_ref, qconst_ref, qcnt_ref, coord_ref,  # [Qb, TB]/[Qb, C+1]
                         scores_out, docs_out, totals_out,  # [1, k], [1, k], [1, 1]
                         acc_docs, acc_contrib, acc_cnt=None,  # VMEM scratch [1, P]
                         *, k: int, doc_pad: int, passes: int, simple: bool,
                         use_coord: bool, TB: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    q = pl.program_id(0)
    t = pl.program_id(1)

    docs = docs_ref[0, :]  # [B] i32 — the qblk-selected block row
    tf = tf_ref[0, :].astype(jnp.float32)  # quantized plane widened in-scan
    nb = nb_ref[0, :].astype(jnp.int32)  # per-posting norm byte
    # LUT decode as a masked broadcast-sum (the one-hot form of cache[nb]):
    # exactly one lane matches per posting, every other addend is +0.0, so the
    # result is bit-identical to the composed path's gather — and it lowers to
    # VPU compare+select instead of a generic gather
    iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 256), 1)
    cv = jnp.sum(jnp.where(nb[:, None] == iota, cache_ref[0, :][None, :], 0.0),
                 axis=1)
    # tf factor first, then weight — the scoring.sparse_candidates op order
    tfn = jnp.where(qmode_s[q, t] == TFN_BM25, tf / (tf + cv),
                    jnp.sqrt(tf) * cv)
    w = qw_ref[q, t]
    contrib = w * jnp.where(qconst_ref[q, t] != 0, 1.0, tfn)
    valid = docs < doc_pad
    contrib = jnp.where(valid, contrib, 0.0)

    acc_docs[0, pl.ds(t * BLOCK, BLOCK)] = docs
    acc_contrib[0, pl.ds(t * BLOCK, BLOCK)] = contrib
    if not simple:
        acc_cnt[0, pl.ds(t * BLOCK, BLOCK)] = jnp.where(
            valid, qcnt_ref[q, t], 0)

    @pl.when(t == TB - 1)
    def _finish():  # the query's candidates are complete — reduce in VMEM
        from .scoring import sparse_reduce

        d = acc_docs[0, :][None, :]  # [1, P]
        c = acc_contrib[0, :][None, :]
        n = None if simple else acc_cnt[0, :][None, :]
        top_scores, top_docs, total = sparse_reduce(
            d, c, n, n_must_s[q][None], msm_s[q][None],
            coord_ref[q, :][None, :], k=k, doc_pad=doc_pad, passes=passes,
            simple=simple, use_coord=use_coord)
        scores_out[0, :] = top_scores[0]
        docs_out[0, :] = top_docs[0]
        totals_out[0, 0] = total[0]


def _sparse_score_call(qblk, qw, qconst, qcnt, qfid, qmode, n_must, msm, coord,
                       blk_docs, blk_tf, blk_nb, caches, *, k: int,
                       doc_pad: int, passes: int, simple: bool,
                       use_coord: bool, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Qb, TB = qblk.shape
    P = TB * BLOCK
    C1 = coord.shape[1]
    kern = functools.partial(_sparse_score_kernel, k=k, doc_pad=doc_pad,
                             passes=passes, simple=simple, use_coord=use_coord,
                             TB=TB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # qblk, qfid, qmode, n_must, msm
        grid=(Qb, TB),
        in_specs=[
            # the prefetched qblk drives WHICH postings block row each grid
            # cell streams in — this is the gather, as streaming DMA
            pl.BlockSpec((1, BLOCK), lambda q, t, qblk, qfid, *_: (qblk[q, t], 0)),
            pl.BlockSpec((1, BLOCK), lambda q, t, qblk, qfid, *_: (qblk[q, t], 0)),
            pl.BlockSpec((1, BLOCK), lambda q, t, qblk, qfid, *_: (qblk[q, t], 0)),
            # the prefetched qfid drives WHICH similarity LUT row rides along
            pl.BlockSpec((1, 256), lambda q, t, qblk, qfid, *_: (qfid[q, t], 0)),
            pl.BlockSpec((Qb, TB), lambda q, t, *_: (0, 0)),  # qw
            pl.BlockSpec((Qb, TB), lambda q, t, *_: (0, 0)),  # qconst (i32)
            pl.BlockSpec((Qb, TB), lambda q, t, *_: (0, 0)),  # qcnt
            pl.BlockSpec((Qb, C1), lambda q, t, *_: (0, 0)),  # coord
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda q, t, *_: (q, 0)),
            pl.BlockSpec((1, k), lambda q, t, *_: (q, 0)),
            pl.BlockSpec((1, 1), lambda q, t, *_: (q, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, P), jnp.int32),  # candidate docs
            pltpu.VMEM((1, P), jnp.float32),  # candidate contributions
        ] + ([] if simple else [
            pltpu.VMEM((1, P), jnp.int32),  # folded group counters
        ]),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qb, k), jnp.float32),
            jax.ShapeDtypeStruct((Qb, k), jnp.int32),
            jax.ShapeDtypeStruct((Qb, 1), jnp.int32),
        ],
        interpret=interpret,
    )(qblk, qfid, qmode, n_must, msm,
      blk_docs, blk_tf, blk_nb, caches, qw, qconst, qcnt, coord)


def sparse_score(qblk, qw, qconst, qcnt, qfid, qmode, n_must, msm, coord,
                 blk_docs, blk_tf, blk_nb, caches, *, k: int, doc_pad: int,
                 passes: int, simple: bool, use_coord: bool):
    """Fused quantized sparse scoring: one pass over the selected block rows →
    per-query ([Qb, k] scores, [Qb, k] docs, [Qb] totals).

    Drop-in equivalent of `scoring.sparse_candidates` + `scoring.sparse_reduce`
    (asserted bitwise by tests/test_pallas_kernels.py); the candidate matrix
    stays in a VMEM accumulator instead of round-tripping HBM."""
    import jax.numpy as jnp

    # ESTPU_PALLAS=interpret forces interpretation EVERYWHERE (incl. on TPU —
    # that's the escape hatch for comparing interpreted vs compiled output);
    # nothing else interprets
    interpret = os.environ.get("ESTPU_PALLAS") == "interpret"
    if not interpret:
        _require_tpu()
    scores, docs, totals = _sparse_score_call(
        jnp.asarray(qblk, jnp.int32), jnp.asarray(qw, jnp.float32),
        jnp.asarray(qconst).astype(jnp.int32),
        jnp.asarray(qcnt, jnp.int32), jnp.asarray(qfid, jnp.int32),
        jnp.asarray(qmode, jnp.int32), jnp.asarray(n_must, jnp.int32),
        jnp.asarray(msm, jnp.int32), jnp.asarray(coord, jnp.float32),
        blk_docs, blk_tf, blk_nb, caches,
        k=k, doc_pad=doc_pad, passes=passes, simple=simple,
        use_coord=use_coord, interpret=interpret)
    return scores, docs, totals[:, 0]
