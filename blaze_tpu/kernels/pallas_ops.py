"""Pallas kernels (see package docstring for the inventory).

Layout convention: a logical row vector of length N is padded to a
multiple of ``TILE_ROWS*LANES`` (=1024) and viewed as an (M, 128)
array; the grid walks blocks of ``TILE_ROWS`` sublane-rows.  All
arithmetic inside kernels is 32-bit (TPU-native); 64-bit key columns
enter as separate low/high uint32 word planes.

Kernels use the output-revisit accumulation pattern (every grid step
maps to the same output block, initialized at step 0) instead of
scratch+copy so the same code runs under ``interpret=True`` on CPU for
tests (tests/test_pallas.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _x32():
    """Trace pallas calls with x64 OFF.

    The engine enables jax_enable_x64 globally (decimals/sums are
    int64/float64), but under x64 Mosaic's grid path emits 64-bit index
    arithmetic it cannot legalize ("failed to legalize func.return").
    Every kernel here is 32-bit end to end, so tracing them in an
    x64-off scope is value-preserving.
    """
    return jax.enable_x64(False)


LANES = 128
TILE_ROWS = 8
TILE = TILE_ROWS * LANES


def _pl():
    from jax.experimental import pallas as pl

    return pl


_FORCE_INTERPRET = False  # tests: exercise kernels off-TPU via interpret mode


def force_interpret(flag: bool) -> None:
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = flag


def available() -> bool:
    """True when the kernels can run (real TPU, or forced interpret)."""
    return _FORCE_INTERPRET or jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode is something only ``force_interpret(True)``
    (tests) turns on: everywhere else a kernel compiles for the
    backend in force or raises."""
    return _FORCE_INTERPRET


# ---------------------------------------------------------------- helpers


def _pad_plane(a: jnp.ndarray, fill) -> jnp.ndarray:
    """(N,) -> (M, 128) with M*128 a multiple of TILE, padded with fill."""
    n = a.shape[0]
    padded = ((n + TILE - 1) // TILE) * TILE
    if padded != n:
        a = jnp.pad(a, (0, padded - n), constant_values=fill)
    return a.reshape(-1, LANES)


# ---------------------------------------------------------------- murmur3

# Spark's Murmur3_x86_32 (seed 42): the bit-exactness-critical mix
# primitives are imported from exprs/hash.py (single source of truth;
# they are pure jnp and trace fine inside a pallas kernel).  The
# kernel's contribution is fusion: hashing K key columns is one HBM
# read of each plane and one HBM write of the pids.
from ..exprs.hash import _fmix, _mix_h1, _mix_k1, _normalize_float  # noqa: E402
from ..schema import TypeKind  # noqa: E402

#: key kinds by uint32 word planes (floats enter as their bit view)
_ONE_WORD = frozenset({TypeKind.BOOL, TypeKind.INT8, TypeKind.INT16,
                       TypeKind.INT32, TypeKind.DATE32})
_TWO_WORDS = frozenset({TypeKind.INT64, TypeKind.TIMESTAMP, TypeKind.DECIMAL})


def _murmur3_pids_kernel(n_parts: int, widths: Tuple[int, ...], *refs):
    """refs = [plane0, plane1, ..., valid0, valid1, ..., out].

    widths[i] in (1, 2): number of uint32 word planes of key column i.
    valids are uint32 (1 = valid); one per key column.
    """
    n_cols = len(widths)
    n_planes = sum(widths)
    planes = refs[:n_planes]
    valids = refs[n_planes : n_planes + n_cols]
    out = refs[-1]

    h = jnp.full(planes[0].shape, np.uint32(42), jnp.uint32)
    pi = 0
    for ci, w in enumerate(widths):
        if w == 1:
            hv = _fmix(_mix_h1(h, _mix_k1(planes[pi][...])), np.uint32(4))
        else:
            h1 = _mix_h1(h, _mix_k1(planes[pi][...]))
            h1 = _mix_h1(h1, _mix_k1(planes[pi + 1][...]))
            hv = _fmix(h1, np.uint32(8))
        pi += w
        h = jnp.where(valids[ci][...] != 0, hv, h)

    signed = jax.lax.bitcast_convert_type(h, jnp.int32)
    m = signed % np.int32(n_parts)
    out[...] = jnp.where(m < 0, m + np.int32(n_parts), m)


def murmur3_pids(
    planes: Sequence[jnp.ndarray],
    widths: Sequence[int],
    valids: Sequence[jnp.ndarray],
    n_parts: int,
) -> jnp.ndarray:
    """Fused Spark murmur3(seed 42) + pmod partition ids.

    planes: flat list of (N,) uint32 word planes (LE words; int32-like
    columns contribute 1 plane, int64-like 2 planes low-then-high).
    valids: one (N,) uint32/bool plane per key column.
    Returns (N,) int32 pids.
    """
    n = planes[0].shape[0]
    in_planes = [_pad_plane(p.astype(jnp.uint32), 0) for p in planes]
    in_valids = [_pad_plane(v.astype(jnp.uint32), 0) for v in valids]
    m = in_planes[0].shape[0]
    call = _build_murmur3_pids(n_parts, tuple(widths), m, _interpret())
    with _x32():
        out = call(*in_planes, *in_valids)
    return out.reshape(-1)[:n]


@functools.lru_cache(maxsize=256)
def _build_murmur3_pids(n_parts: int, widths: Tuple[int, ...], m: int, interpret: bool):
    """Cached pallas_call construction — jit caches by callable
    identity, so rebuilding per batch would re-trace every call."""
    pl = _pl()
    spec = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    n_in = sum(widths) + len(widths)
    return pl.pallas_call(
        functools.partial(_murmur3_pids_kernel, n_parts, widths),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.int32),
        grid=(m // TILE_ROWS,),
        in_specs=[spec] * n_in,
        out_specs=spec,
        interpret=interpret,
    )


def key_type_supported(dtype) -> bool:
    """Does a key column of ``dtype`` have a word-plane form
    (:func:`column_word_planes`)?  Fixed-width scalars do; strings and
    nested types do not — their callers hash with XLA.  Decided from
    the TYPE before any kernel is built, so a kernel that then fails to
    lower or compile is a failure, never a fallback."""
    return dtype.is_float or dtype.kind in _ONE_WORD or dtype.kind in _TWO_WORDS


def column_word_planes(col) -> Tuple[List[jnp.ndarray], int]:
    """Split a Column's data into uint32 word planes for murmur3_pids.

    Returns (planes, width).  Only the types ``key_type_supported``
    admits.
    """
    k = col.dtype.kind
    d = col.data
    if not key_type_supported(col.dtype):
        raise NotImplementedError(f"murmur3 pallas path over {col.dtype!r}")
    if col.dtype.is_float:
        d, k = _normalize_float(col)  # -0.0 normalize + bit view (hash.py)
    if k in _ONE_WORD:
        return [d.astype(jnp.int32).view(jnp.uint32)], 1
    v = d.astype(jnp.int64)
    low = (v & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    high = ((v >> jnp.int64(32)) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return [low, high], 2


# ---------------------------------------------------------------- histogram


def _histogram_kernel(p_pad: int, pids_ref, out_ref):
    pl = _pl()
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p_iota = jax.lax.broadcasted_iota(jnp.int32, (p_pad, LANES), 0)
    acc = out_ref[...]
    for r in range(TILE_ROWS):
        row = pids_ref[r : r + 1, :]  # (1, 128): keep 2-D for mosaic
        acc = acc + (p_iota == row).astype(jnp.int32)
    out_ref[...] = acc


def pid_histogram(pids: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Per-partition row counts in one pass (padding rows enter as -1
    and match no partition).  Returns (n_parts,) int32.

    ≙ the per-partition counts SortShuffleRepartitioner derives when
    laying out partition runs (sort_repartitioner.rs); XLA would lower
    the equivalent scatter-add as sort + segment-sum.
    """
    p_pad = max(8, ((n_parts + 7) // 8) * 8)
    planes = _pad_plane(pids.astype(jnp.int32), -1)
    m = planes.shape[0]
    call = _build_histogram(p_pad, m, _interpret())
    with _x32():
        out = call(planes)
    return jnp.sum(out, axis=1)[:n_parts]


@functools.lru_cache(maxsize=256)
def _build_histogram(p_pad: int, m: int, interpret: bool):
    pl = _pl()
    return pl.pallas_call(
        functools.partial(_histogram_kernel, p_pad),
        out_shape=jax.ShapeDtypeStruct((p_pad, LANES), jnp.int32),
        grid=(m // TILE_ROWS,),
        in_specs=[pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((p_pad, LANES), lambda i: (0, 0)),
        interpret=interpret,
    )


# ------------------------------------------------------ grouped aggregation


def _group_sums_kernel(g_pad: int, n_vals: int, *refs):
    """refs = [gids, v0..v{K-1}, out(K, g_pad, LANES)]."""
    pl = _pl()
    gids_ref = refs[0]
    val_refs = refs[1 : 1 + n_vals]
    out_ref = refs[-1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g_iota = jax.lax.broadcasted_iota(jnp.int32, (g_pad, LANES), 0)
    # per-k running sums (static-indexed loads/stores; .at[k].add would
    # lower as an unsupported scatter-add)
    accs = [out_ref[k] for k in range(n_vals)]
    for r in range(TILE_ROWS):
        onehot = (g_iota == gids_ref[r : r + 1, :]).astype(jnp.float32)
        for k in range(n_vals):
            accs[k] = accs[k] + onehot * val_refs[k][r : r + 1, :]
    for k in range(n_vals):
        out_ref[k] = accs[k]


def fused_group_sums(
    gids: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    n_groups: int,
) -> jnp.ndarray:
    """Small-cardinality grouped sums in one fused pass.

    gids: (N,) int32 group ids; rows failing the predicate (or padding)
    carry gid -1 and contribute nothing — the caller folds its filter
    into the gid assignment, so scan->filter->agg is ONE kernel.
    values: K arrays (N,) float32.  Returns (K, n_groups) float32.
    """
    g_pad = max(8, ((n_groups + 7) // 8) * 8)
    gid_planes = _pad_plane(gids.astype(jnp.int32), -1)
    val_planes = [_pad_plane(v.astype(jnp.float32), 0) for v in values]
    m = gid_planes.shape[0]
    k = len(values)
    call = _build_group_sums(g_pad, k, m, _interpret())
    with _x32():
        out = call(gid_planes, *val_planes)
    return jnp.sum(out, axis=2)[:, :n_groups]


@functools.lru_cache(maxsize=256)
def _build_group_sums(g_pad: int, k: int, m: int, interpret: bool):
    pl = _pl()
    spec = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_group_sums_kernel, g_pad, k),
        out_shape=jax.ShapeDtypeStruct((k, g_pad, LANES), jnp.float32),
        grid=(m // TILE_ROWS,),
        in_specs=[spec] * (1 + k),
        out_specs=pl.BlockSpec((k, g_pad, LANES), lambda i: (0, 0, 0)),
        interpret=interpret,
    )

