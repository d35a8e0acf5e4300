"""Decomposed (overlapped) collective matmuls — the beyond-paper TPU analogue
of NanoFlow's network/compute overlap (DESIGN.md §2).

XLA *can* overlap async collectives, but an un-decomposed AllGather→GEMM
chain leaves the full gather on the critical path.  Decomposing into
``chunks`` ring steps (chunk count = the nano-batch count chosen by
core/autosearch) hides all but one chunk's ICI latency behind the MXU:

  allgather_matmul:       Y_loc = concat_p(x_p) @ W_loc  (W column-parallel)
  matmul_reduce_scatter:  Y_p   = Σ_p' (x @ W)_p'        (W row-parallel)

Both are written for use inside ``jax.shard_map`` over one mesh axis and are
bit-compatible with the naive collective + matmul (tested on host devices).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def allgather_matmul(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """x: (m, k_local) — feature-sharded on `axis_name`;
    w: (k_total, n_local) — each device holds ALL rows for its column shard.
    Returns x_full @ w (m, n_local) without materializing x_full: each ring
    step multiplies the chunk in hand while the next chunk is in flight.
    """
    p = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    m, k_local = x.shape
    assert w.shape[0] == k_local * p, (x.shape, w.shape)

    def rows(i):
        # rows of w corresponding to the chunk that originated at device i
        return jax.lax.dynamic_slice_in_dim(w, i * k_local, k_local, axis=0)

    def body(step, carry):
        acc, chunk, src = carry
        acc = acc + jnp.dot(chunk, rows(src),
                            preferred_element_type=jnp.float32)
        # pass our chunk around the ring; after step s we hold (idx+s+1)'s
        nxt = jax.lax.ppermute(
            chunk, axis_name, [(j, (j - 1) % p) for j in range(p)])
        return acc, nxt, (src + 1) % p

    # the ring accumulator differs per device: tag it varying for
    # shard_map's replication (vma) check
    acc = jax.lax.pcast(jnp.zeros((m, w.shape[1]), jnp.float32),
                        (axis_name,), to="varying")
    acc, chunk, src = jax.lax.fori_loop(0, p - 1, body, (acc, x, idx))
    acc = acc + jnp.dot(chunk, rows(src), preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name: str,
                          scatter_dim: int = 1) -> jax.Array:
    """x: (m, k_local); w: (k_local, n) row-parallel shard.  Computes the
    full partial product then reduce-scatters columns across `axis_name`,
    chunk-by-chunk so each ring transfer overlaps the next chunk's GEMM.

    Returns (m, n/p): the column shard of the summed product.
    """
    p = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    m, k_local = x.shape
    n = w.shape[1]
    assert n % p == 0, (n, p)
    nc = n // p

    def cols(i):
        return jax.lax.dynamic_slice_in_dim(w, i * nc, nc, axis=1)

    # ring reduce-scatter: the packet for column chunk c starts at device
    # c+1 and flows toward increasing ids, so device j adds its contribution
    # for chunk (j-1-s) at step s; after p-1 hops it holds its own chunk.
    def body(step, carry):
        acc, dst = carry
        acc = acc + jnp.dot(x, cols(dst), preferred_element_type=jnp.float32)
        nxt = jax.lax.ppermute(
            acc, axis_name, [(j, (j + 1) % p) for j in range(p)])
        return nxt, (dst - 1) % p

    start = (idx - 1) % p
    acc = jax.lax.pcast(jnp.zeros((m, nc), jnp.float32), (axis_name,),
                        to="varying")
    acc, dst = jax.lax.fori_loop(0, p - 1, body, (acc, start))
    # dst == idx now: add our own contribution last
    acc = acc + jnp.dot(x, cols(dst), preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


def matmul_allreduce(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Row-parallel matmul + AR = reduce-scatter matmul + all-gather (the
    all-gather chunks also overlap).  Drop-in for `psum(x @ w)`."""
    part = matmul_reduce_scatter(x, w, axis_name)
    return jax.lax.all_gather(part, axis_name, axis=1, tiled=True)
