"""Blocked segment-reduction (arrival counting) as a Pallas kernel.

Companion to :mod:`repro.kernels.tropical`: where the tropical kernel powers
the *server-side* round recurrence, this one powers the *client-side* batch
formation in ``repro.vecsim.clients``.  The quantity it computes is the
arrival-count prefix

    counts[..., k] = #{ j : s[..., j] <= edges[..., k] }

i.e. for every round-entry edge ``edges[k]`` of a server's timeline, how many
of that server's client submit times ``s[j]`` have arrived by then.  Batch
formation then reduces to a tiny scan over ``counts`` (see
``vecsim/README.md``); this kernel is the only part that touches the
million-client axis.

Tiling: each batch row's client axis is laid out as a 2-D ``(rows, 128)``
tile, so every block meets the TPU's (8, 128) rule.  The grid runs over
(batch, K-blocks, client blocks); the client blocks are the reduction axis
and accumulate into the output block.  Inside a step the edges are scalars
read from SMEM, and each one is compared against the whole client tile at
once.  Adding batch dimensions under ``vmap`` adds one more parallel grid
axis.

Exactness: the reduction is an integer sum of exact float comparisons, so
the kernel is *bit-for-bit* equal to the jnp reference
(:func:`segment_counts_reference`, a searchsorted over the sorted submit
times) for finite ``edges``.  Submit times may include ``+inf`` entries
(padding for ragged client populations) — they compare False against every
finite edge and contribute nothing.  ``edges`` must be finite and NaN-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import CompilerParams
from .tropical import resolve_interpret


_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _segred_kernel(e_ref, s_ref, o_ref, *, block_k: int):
    # e_ref: (1, 1, bk) edges in SMEM; s_ref: (1, br, 128) client tile;
    # o_ref: (1, 1, bk) int32 counts, accumulated over client blocks
    @pl.when(pl.program_id(2) == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    s = s_ref[0]                                   # (br, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    def body(k, acc):
        hit = (s <= e_ref[0, 0, k]).astype(jnp.int32)
        cnt = jnp.sum(hit, axis=(0, 1), keepdims=True, dtype=jnp.int32)
        return acc + jnp.where(lane == k, cnt, 0)

    acc = jax.lax.fori_loop(0, block_k, body,
                            jnp.zeros((1, block_k), jnp.int32))
    o_ref[0] += acc


def segment_counts(s, edges, *, block_k: int = 128, block_m: int = 131072,
                   interpret: bool | None = None):
    """``counts[..., k] = #{j : s[..., j] <= edges[..., k]}`` as int32.

    ``s``: (..., M) submit times, any order, ``+inf`` allowed as padding;
    ``edges``: (..., K) finite edge times, leading dims matching ``s``.
    ``block_m`` clients per grid step (rounded to whole (8, 128) tiles).
    Bit-for-bit equal to :func:`segment_counts_reference`.
    """
    s = jnp.asarray(s)
    edges = jnp.asarray(edges)
    if s.ndim < 1 or edges.ndim < 1 or s.shape[:-1] != edges.shape[:-1]:
        raise ValueError(f"batch mismatch: {s.shape} x {edges.shape}")
    dtype = jnp.promote_types(s.dtype, edges.dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        dtype = jnp.float32
    interpret = resolve_interpret(interpret, dtype)
    batch_shape = s.shape[:-1]
    m, k = s.shape[-1], edges.shape[-1]
    B = 1
    for d in batch_shape:
        B *= d

    rows = _round_up(max(-(-m // _LANES), 1), 8)
    br = min(_round_up(max(-(-block_m // _LANES), 1), 8), rows)
    rows = _round_up(rows, br)
    bk = min(block_k, max(k, 1))
    kp = _round_up(k, bk)
    sf = jnp.pad(s.astype(dtype).reshape(B, m),
                 ((0, 0), (0, rows * _LANES - m)),
                 constant_values=jnp.inf).reshape(B, rows, _LANES)
    # padded edge columns are sliced off; -inf keeps them at zero counts
    ef = jnp.pad(edges.astype(dtype).reshape(B, 1, k),
                 ((0, 0), (0, 0), (0, kp - k)), constant_values=-jnp.inf)

    out = pl.pallas_call(
        functools.partial(_segred_kernel, block_k=bk),
        grid=(B, kp // bk, rows // br),
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda bi, ki, mi: (bi, 0, ki),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, br, _LANES), lambda bi, ki, mi: (bi, mi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bk), lambda bi, ki, mi: (bi, 0, ki)),
        out_shape=jax.ShapeDtypeStruct((B, 1, kp), jnp.int32),
        interpret=interpret,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(ef, sf)
    return out[:, 0, :k].reshape(batch_shape + (k,))


def segment_counts_reference(s, edges):
    """jnp reference: searchsorted of ``edges`` over the sorted submit times.

    Mathematically identical to :func:`segment_counts` (both are exact
    integer counts of exact float comparisons); used as the bitexactness
    oracle in tests and as the ``engine="vec"`` path in vecsim.clients.
    """
    s = jnp.asarray(s)
    edges = jnp.asarray(edges)
    s_sorted = jnp.sort(s, axis=-1)
    if s.ndim == 1:
        return jnp.searchsorted(s_sorted, edges, side="right").astype(jnp.int32)
    flat_s = s_sorted.reshape((-1, s.shape[-1]))
    flat_e = edges.reshape((-1, edges.shape[-1]))
    counts = jax.vmap(
        lambda a, b: jnp.searchsorted(a, b, side="right"))(flat_s, flat_e)
    return counts.astype(jnp.int32).reshape(edges.shape)
