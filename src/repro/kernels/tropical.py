"""Tropical-semiring (min-plus) matmul / relaxation as a Pallas kernel.

The failure-free AllConcur+/AllConcur round recurrence reduces to iterated
min-plus products ``T[s, v] = min_u(T[s, u] + cost[u, v])`` (see
``repro.vecsim.engine``).  This module lowers that contraction onto the same
Pallas layer as the attention/scan kernels:

- :func:`tropical_matmul` — blocked ``min_k(A[ik] + B[kj])`` with +inf-aware
  tiling.  Leading batch dimensions on ``A`` (and optionally ``B``) map onto
  a parallel grid axis, so one ``pallas_call`` relaxes a whole round-batch.
- :func:`tropical_matmul_threshold` — the fused variant the G_R engine
  needs: alongside the plain min it returns ``min_k(f(A+B))`` where
  ``f(x) = x if x >= thresh else big``, replicating the event semantics of
  "a copy arriving before the round entry cannot be installed".
- :func:`tropical_relax` / :func:`tropical_closure` — iterated-relaxation
  entry points (Bellman-Ford steps, and the Kleene star by repeated
  squaring).

Tiling: the grid is purely parallel over (batch, M-blocks, N-blocks); the
contraction axis is staged into VMEM once per tile and reduced with a
``fori_loop`` over ``block_k`` slices, which bounds the materialized
``(block_m, block_k, block_n)`` intermediate (min-plus has no MXU path — the
broadcast-add + min runs on the VPU).  A purely parallel grid keeps the
kernel ``vmap``-safe: the engine's per-config ``vmap`` adds one more grid
axis without touching any cross-step scratch state.

Exactness: min and broadcast-add are exact in floating point, so the kernel
is *bit-for-bit* equal to a jnp reference over the same candidate set — the
property the vecsim cross-validation relies on.  Entries may be ``+inf``
(non-edges, padding) but not ``-inf``/NaN.  On CPU run ``interpret=True``
(float64 works); compiled on a TPU the data must be float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compat import CompilerParams

BIG = 1e12   # default below-threshold replacement (matches vecsim.engine.BIG)


def resolve_interpret(interpret: bool | None, dtype) -> bool:
    """Interpret mode by default off the TPU only (the CPU tests).

    A compiled kernel never falls back to the interpreter: float64 data with
    ``interpret=False`` (the default on a TPU) is refused here, because
    Pallas on a TPU has no float64.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and jnp.dtype(dtype) == jnp.float64:
        raise ValueError("Pallas on a TPU has no float64: pass float32 data "
                         "or run with interpret=True off the chip")
    return interpret


def _minplus_chunk(a_ref, b_ref, k0, width, acc, thr=None, big=None):
    """Fold ``width`` contraction columns starting at ``k0`` into ``acc``.

    The chunk is read from the refs with ``pl.ds`` and split into static
    single-column slices, so every candidate is a (bm, 1) lane broadcast plus
    a (1, bn) sublane broadcast: 2-D ops only, which Mosaic lowers directly.
    """
    ab = a_ref[0, :, pl.ds(k0, width)]            # (bm, width)
    bb = b_ref[0, pl.ds(k0, width), :]            # (width, bn)
    for kk in range(width):
        cand = ab[:, kk:kk + 1] + bb[kk:kk + 1, :]    # (bm, bn)
        if thr is None:
            acc = jnp.minimum(acc, cand)
        else:
            plain, gated = acc
            acc = (jnp.minimum(plain, cand),
                   jnp.minimum(gated, jnp.where(cand >= thr, cand, big)))
    return acc


def _tropical_kernel(a_ref, b_ref, o_ref, *, block_k: int, nk: int):
    def body(ki, acc):
        return _minplus_chunk(a_ref, b_ref, ki * block_k, block_k, acc)

    acc0 = jnp.full(o_ref.shape[1:], jnp.inf, o_ref.dtype)
    o_ref[0] = jax.lax.fori_loop(0, nk, body, acc0)


def _tropical_threshold_kernel(a_ref, b_ref, t_ref, o_ref, othr_ref, *,
                               block_k: int, nk: int, big: float):
    t = t_ref[0]                                  # (bm, bn)

    def body(ki, accs):
        return _minplus_chunk(a_ref, b_ref, ki * block_k, block_k, accs,
                              thr=t, big=big)

    acc0 = jnp.full(o_ref.shape[1:], jnp.inf, o_ref.dtype)
    out, out_thr = jax.lax.fori_loop(0, nk, body, (acc0, acc0))
    o_ref[0] = out
    othr_ref[0] = out_thr


def _prep(a, b, thresh, block_m, block_n, block_k):
    """Normalize shapes/dtypes and pad to tile multiples (+inf padding)."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    dtype = jnp.promote_types(a.dtype, b.dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        dtype = jnp.float32
    a = a.astype(dtype)
    b = b.astype(dtype)
    if a.ndim < 2 or b.ndim < 2:
        raise ValueError(f"need matrices, got {a.shape} x {b.shape}")
    batch_shape = a.shape[:-2]
    m, k = a.shape[-2:]
    if b.shape[-2] != k:
        raise ValueError(f"contraction mismatch: {a.shape} x {b.shape}")
    n = b.shape[-1]
    b_batched = b.ndim > 2
    if b_batched and b.shape[:-2] != batch_shape:
        raise ValueError(f"batch mismatch: {a.shape} x {b.shape}")
    B = 1
    for s in batch_shape:
        B *= s

    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    af = jnp.pad(a.reshape(B, m, k), ((0, 0), (0, pm), (0, pk)),
                 constant_values=jnp.inf)
    bf = b.reshape(B if b_batched else 1, k, n)
    bf = jnp.pad(bf, ((0, 0), (0, pk), (0, pn)), constant_values=jnp.inf)
    tf = None
    if thresh is not None:
        tf = jnp.broadcast_to(jnp.asarray(thresh, dtype),
                              batch_shape + (m, n)).reshape(B, m, n)
        tf = jnp.pad(tf, ((0, 0), (0, pm), (0, pn)))
    dims = dict(B=B, m=m, n=n, bm=bm, bn=bn, bk=bk,
                mp=m + pm, np=n + pn, kp=k + pk,
                batch_shape=batch_shape, b_batched=b_batched, dtype=dtype)
    return af, bf, tf, dims


def _call(kernel, af, bf, tf, d, interpret, n_out):
    grid = (d["B"], d["mp"] // d["bm"], d["np"] // d["bn"])
    nk = d["kp"] // d["bk"]
    a_spec = pl.BlockSpec((1, d["bm"], d["kp"]), lambda bi, mi, ni: (bi, mi, 0))
    if d["b_batched"]:
        b_spec = pl.BlockSpec((1, d["kp"], d["bn"]),
                              lambda bi, mi, ni: (bi, 0, ni))
    else:
        b_spec = pl.BlockSpec((1, d["kp"], d["bn"]),
                              lambda bi, mi, ni: (0, 0, ni))
    mn_spec = pl.BlockSpec((1, d["bm"], d["bn"]),
                           lambda bi, mi, ni: (bi, mi, ni))
    out_sds = jax.ShapeDtypeStruct((d["B"], d["mp"], d["np"]), d["dtype"])
    in_specs = [a_spec, b_spec] + ([mn_spec] if tf is not None else [])
    out = pl.pallas_call(
        functools.partial(kernel, block_k=d["bk"], nk=nk),
        grid=grid,
        in_specs=in_specs,
        out_specs=mn_spec if n_out == 1 else [mn_spec] * n_out,
        out_shape=out_sds if n_out == 1 else [out_sds] * n_out,
        interpret=interpret,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",) * 3),
    )(*([af, bf] + ([tf] if tf is not None else [])))
    outs = (out,) if n_out == 1 else tuple(out)
    shaped = tuple(o[:, :d["m"], :d["n"]].reshape(
        d["batch_shape"] + (d["m"], d["n"])) for o in outs)
    return shaped[0] if n_out == 1 else shaped


def tropical_matmul(a, b, *, block_m: int = 128, block_n: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Min-plus product ``out[.., i, j] = min_k(a[.., i, k] + b[.., k, j])``.

    ``a``: (..., M, K); ``b``: (K, N) shared across the batch, or (..., K, N)
    matching ``a``'s leading dims.  +inf entries (non-edges / padding) are
    handled exactly; the result is bit-for-bit equal to the jnp reference.
    """
    af, bf, _tf, d = _prep(a, b, None, block_m, block_n, block_k)
    interpret = resolve_interpret(interpret, d["dtype"])
    return _call(_tropical_kernel, af, bf, None, d, interpret, 1)


def tropical_matmul_threshold(a, b, thresh, *, big: float = BIG,
                              block_m: int = 128, block_n: int = 128,
                              block_k: int = 128,
                              interpret: bool | None = None):
    """Fused plain + thresholded min-plus product.

    Returns ``(plain, gated)`` where ``plain`` is :func:`tropical_matmul` and
    ``gated[.., i, j] = min_k(f(a[.., i, k] + b[.., k, j]))`` with
    ``f(x) = x if x >= thresh[.., i, j] else big`` — each candidate below the
    threshold contributes exactly ``big`` (not +inf), matching the vecsim
    G_R install rule where an early copy is replaced by a BIG sentinel.
    ``thresh`` broadcasts against the (..., M, N) output.
    """
    af, bf, tf, d = _prep(a, b, thresh, block_m, block_n, block_k)
    interpret = resolve_interpret(interpret, d["dtype"])
    kernel = functools.partial(_tropical_threshold_kernel, big=big)
    return _call(kernel, af, bf, tf, d, interpret, 2)


def tropical_relax(t0, cost, *, iters: int, interpret: bool | None = None,
                   **blocks):
    """``iters`` Bellman-Ford relaxation steps ``T <- min(T, T (x) cost)``.

    ``t0``: (..., M, N) current tentative distances; ``cost``: (N, N) edge
    costs (+inf for non-edges).  With ``iters >= N-1`` this reaches the
    min-plus fixpoint (all-pairs-from-sources shortest paths).
    """
    t = jnp.asarray(t0)
    for _ in range(iters):
        t = jnp.minimum(t, tropical_matmul(t, cost, interpret=interpret,
                                           **blocks))
    return t


def tropical_closure(cost, *, interpret: bool | None = None, **blocks):
    """Kleene star: shortest-path distances by repeated min-plus squaring.

    ``cost``: (N, N), +inf for non-edges.  Computes ``(I_min ⊕ cost)^(N-1)``
    in ``ceil(log2(N-1))`` squarings, where ``I_min`` has a 0 diagonal.
    """
    cost = jnp.asarray(cost)
    n = cost.shape[-1]
    t = jnp.minimum(cost, jnp.where(jnp.eye(n, dtype=bool), 0.0,
                                    jnp.inf).astype(cost.dtype))
    span = 1
    while span < n - 1:
        t = tropical_matmul(t, t, interpret=interpret, **blocks)
        span *= 2
    return t
