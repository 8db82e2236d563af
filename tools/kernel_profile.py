"""Component timing for the scoring kernel at the bench shape (task: find the 123ms).

Times each stage of ops/scoring.py's fused program in isolation on the live device:
  gather+FMA, scatter-add, top_k (full), top_k (two-stage), sort-based sparse path.
Run: python tools/kernel_profile.py   (needs a TPU: exits non-zero off one unless the
caller set JAX_PLATFORMS=cpu, and then the times are CPU times)
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

Q = 1024
DPAD = 131072
K = 100
BLOCK = 128
M = 32768  # triples, bench-like
NB = 16384


def timeit(fn, *args, n=5):
    r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def main():
    from elasticsearch_tpu.common.jaxenv import require_accelerator

    require_accelerator("kernel_profile")  # exits non-zero off a TPU
    rng = np.random.default_rng(0)
    blk_docs = jnp.asarray(rng.integers(0, DPAD, (NB, BLOCK), dtype=np.int32))
    blk_freqs = jnp.asarray(rng.random((NB, BLOCK), dtype=np.float32) * 5 + 1)
    qidx = jnp.asarray(rng.integers(0, Q, M, dtype=np.int32))
    blk = jnp.asarray(rng.integers(0, NB, M, dtype=np.int32))
    weight = jnp.asarray(rng.random(M, dtype=np.float32))
    norms = jnp.asarray(rng.integers(0, 256, DPAD, dtype=np.uint8))
    cache = jnp.asarray(rng.random(256, dtype=np.float32) + 0.5)

    @jax.jit
    def gather_fma(blk_docs, blk_freqs, blk, weight, norms, cache):
        docs = blk_docs[blk]
        freqs = blk_freqs[blk]
        nb = norms[docs]
        cv = cache[nb.astype(jnp.int32)]
        contrib = (weight[:, None] * freqs) / (freqs + cv)
        return docs, contrib

    t = timeit(gather_fma, blk_docs, blk_freqs, blk, weight, norms, cache)
    print(f"gather+FMA [{M}x{BLOCK}]: {t*1000:.2f} ms")

    docs, contrib = gather_fma(blk_docs, blk_freqs, blk, weight, norms, cache)

    @jax.jit
    def scatter(docs, contrib, qidx):
        flat = qidx[:, None] * (DPAD + 1) + docs
        return jnp.zeros(Q * (DPAD + 1), jnp.float32).at[flat.reshape(-1)].add(
            contrib.reshape(-1), mode="drop").reshape(Q, DPAD + 1)[:, :DPAD]

    t = timeit(scatter, docs, contrib, qidx)
    print(f"scatter-add into [Q,{DPAD}]: {t*1000:.2f} ms")

    scores = scatter(docs, contrib, qidx)

    @jax.jit
    def topk_full(scores):
        return jax.lax.top_k(scores, K)

    t = timeit(topk_full, scores)
    print(f"top_k full [Q,{DPAD}] k={K}: {t*1000:.2f} ms")

    @jax.jit
    def topk_2stage(scores):
        CH = 64
        s = scores.reshape(Q * CH, DPAD // CH)
        s1, d1 = jax.lax.top_k(s, K)
        s1 = s1.reshape(Q, CH * K)
        base = (jnp.arange(CH, dtype=jnp.int32) * (DPAD // CH))[None, :, None]
        d1 = (d1.reshape(Q, CH, K) + base).reshape(Q, CH * K)
        s2, i2 = jax.lax.top_k(s1, K)
        return s2, jnp.take_along_axis(d1, i2, axis=1)

    t = timeit(topk_2stage, scores)
    print(f"top_k 2-stage (64 chunks): {t*1000:.2f} ms")

    # sparse path: per-query candidate rows [Q, P] -> sort by doc -> seg-sum -> top_k
    TB = 32  # blocks per query
    P = TB * BLOCK  # 4096 candidates
    qblk = jnp.asarray(rng.integers(0, NB, (Q, TB), dtype=np.int32))
    qw = jnp.asarray(rng.random((Q, TB), dtype=np.float32))

    @jax.jit
    def sparse(blk_docs, blk_freqs, qblk, qw, norms, cache):
        docs = blk_docs[qblk]                      # [Q, TB, B]
        freqs = blk_freqs[qblk]
        nb = norms[docs]
        cv = cache[nb.astype(jnp.int32)]
        contrib = (qw[:, :, None] * freqs) / (freqs + cv)
        docs = docs.reshape(Q, P)
        contrib = contrib.reshape(Q, P)
        docs_s, contrib_s = jax.lax.sort((docs, contrib), num_keys=1)
        # run-length <= 4: 2 doubling passes
        for shift in (1, 2):
            same = jnp.concatenate(
                [jnp.zeros((Q, shift), bool), docs_s[:, shift:] == docs_s[:, :-shift]],
                axis=1)
            shifted = jnp.concatenate(
                [jnp.zeros((Q, shift), jnp.float32), contrib_s[:, :-shift]], axis=1)
            contrib_s = contrib_s + jnp.where(same, shifted, 0.0)
        is_last = jnp.concatenate(
            [docs_s[:, :-1] != docs_s[:, 1:], jnp.ones((Q, 1), bool)], axis=1)
        masked = jnp.where(is_last, contrib_s, -jnp.inf)
        s, i = jax.lax.top_k(masked, K)
        return s, jnp.take_along_axis(docs_s, i, axis=1)

    t = timeit(sparse, blk_docs, blk_freqs, qblk, qw, norms, cache)
    print(f"sparse sort path [Q,{P}]: {t*1000:.2f} ms")

    # sparse at 4x candidate volume (P=16384)
    TB2 = 128
    P2 = TB2 * BLOCK
    qblk2 = jnp.asarray(rng.integers(0, NB, (Q, TB2), dtype=np.int32))
    qw2 = jnp.asarray(rng.random((Q, TB2), dtype=np.float32))

    @jax.jit
    def sparse2(blk_docs, blk_freqs, qblk, qw, norms, cache):
        docs = blk_docs[qblk]
        freqs = blk_freqs[qblk]
        nb = norms[docs]
        cv = cache[nb.astype(jnp.int32)]
        contrib = (qw[:, :, None] * freqs) / (freqs + cv)
        docs = docs.reshape(Q, P2)
        contrib = contrib.reshape(Q, P2)
        docs_s, contrib_s = jax.lax.sort((docs, contrib), num_keys=1)
        for shift in (1, 2):
            same = jnp.concatenate(
                [jnp.zeros((Q, shift), bool), docs_s[:, shift:] == docs_s[:, :-shift]],
                axis=1)
            shifted = jnp.concatenate(
                [jnp.zeros((Q, shift), jnp.float32), contrib_s[:, :-shift]], axis=1)
            contrib_s = contrib_s + jnp.where(same, shifted, 0.0)
        is_last = jnp.concatenate(
            [docs_s[:, :-1] != docs_s[:, 1:], jnp.ones((Q, 1), bool)], axis=1)
        masked = jnp.where(is_last, contrib_s, -jnp.inf)
        s, i = jax.lax.top_k(masked, K)
        return s, jnp.take_along_axis(docs_s, i, axis=1)

    t = timeit(sparse2, blk_docs, blk_freqs, qblk2, qw2, norms, cache)
    print(f"sparse sort path [Q,{P2}]: {t*1000:.2f} ms")


if __name__ == "__main__":
    main()
