"""Pallas kernel: fused phase-2 projection-DPP selection (paper Alg. 2).

The reference implementation (``sampling.batched.phase2_select_reference``)
runs the Gram-Schmidt chain rule as a ``lax.while_loop`` of O(k_eff) small
ops — prefix mass -> inverse-CDF search -> factored row gather -> CGS2 ->
one O(N) colspace matvec -> norms downdate — re-reading the factored
columns and the residual-norms vector from HBM every step. This kernel
fuses the whole loop into one ``pallas_call``:

grid        (batch,) — one sample per grid step; inside it a
            ``lax.while_loop`` runs the data-dependent k_eff selection
            steps (k_eff arrives by scalar prefetch into SMEM).
resident    the factor pair, the (K, K) Gram-Schmidt basis (one basis row
            per step), and the (N1p, Nrp) residual norms live in VMEM for
            all k_eff steps.
factors     canonicalized to exactly two: the leading block G1 (N1, k) and
            the elementwise-product fold Gr (Nr, k) of every trailing
            factor (``canonical_pair``); m = 1 gets a ones() leading factor.
            One kernel therefore serves the DPP, k-DPP and dense paths.
layout      ``pad_pair`` pads N1 and k to the 8-row sublane tiling and Nr
            to the 128-lane tiling with zeros: padded items
            carry zero mass and are never selected, padded columns add
            exact zeros to every product. A one-row leading factor
            (m = 1) stays one row: its block equals the full array.

Both engines run one step function, ``select_step``, on the same padded
arrays, so on the CPU their picks agree draw for draw (the property tests
assert equality). The step is written in the operations Mosaic lowers —
no cumsum, no dynamic single-lane stores, every dot at HIGHEST precision —
and draws its item in two levels, so each float32 index stays exact
(below 2**24) for any N the (N1p, Nrp) grid can hold:

row         per-row mass by a fixed fold (lane tiles left to right, then
            halving the 128 lanes), a Hillis-Steele scan of the row
            totals, and the first row whose inclusive prefix exceeds
            ``u · total``;
column      a Hillis-Steele scan of that one row, offset by the mass of
            the rows before it, and its first column past the target.

The two engines do the same float32 adds in the same order and differ
only in how they move data: the kernel shifts whole vregs with
``pltpu.roll`` and gathers rows with iota masks; the XLA engine
(``roll=None``) slices and indexes, so a step costs it about two passes
over the grid (the fold and the norms downdate) instead of a scan of it.

Inverse CDF: the pick is the first positive-mass item whose prefix mass
exceeds ``u · total``; where rounding leaves no such item in the chosen
row (or no such row), the last positive-mass one — never a zero-mass
(already selected) item.

Degenerate spectra: when the total residual mass collapses below
``MASS_EPS`` (numerically rank-deficient factors exhaust the column span
early), the step marks the sample dead instead of picking — remaining
slots stay -1, the loop exits.

``interpret=True`` runs the same kernel as XLA on CPU (tests); on TPU the
``kernels.ops`` wrapper always compiles it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A normalized q-column with squared norm below this is treated as zero
#: (the item was already in the selected span) — matches the reference.
EPS = 1e-30

#: Total residual mass at or below this means the remaining columns span
#: nothing selectable: stop instead of clamp-picking N-1 forever. Healthy
#: steps have mass k_eff - t >= 1, so 1e-6 is many orders conservative.
MASS_EPS = 1e-6

SUBLANE, LANE = 8, 128
_HIGHEST = jax.lax.Precision.HIGHEST
#: Row and column indices are reduced as float32, exact up to 2**24 —
#: ``padded_dims`` refuses a grid axis longer than this. It doubles as
#: the masked-min "no index" sentinel.
MAX_AXIS = 2 ** 24
_NO_POS = float(MAX_AXIS)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def fold_trailing(Gs: Tuple[jax.Array, ...]) -> Tuple[jax.Array, ...]:
    """(G_1, ..., G_m) -> (G_1, G_r): elementwise-product fold of the
    trailing factors, row-major — Gr[(n_2..n_m), c] = prod_{f>1} G_f[n_f, c].
    Works on unbatched (N_f, k) and batched (B, N_f, k) stacks alike."""
    if len(Gs) <= 2:
        return tuple(Gs)
    Gr = Gs[1]
    for G in Gs[2:]:
        k = Gr.shape[-1]
        Gr = (Gr[..., :, None, :] * G[..., None, :, :]).reshape(
            Gr.shape[:-2] + (Gr.shape[-2] * G.shape[-2], k))
    return (Gs[0], Gr)


def canonical_pair(Gs: Tuple[jax.Array, ...]) -> Tuple[jax.Array, jax.Array]:
    """Exactly two factors: fold the trailing ones; for m = 1 synthesize a
    ones() LEADING factor, so the N items lie along the 128-lane axis of
    the norms grid. Shared by the kernel wrapper AND the jax reference so
    both run bit-identical arithmetic (draw-for-draw picks)."""
    Gs = fold_trailing(Gs)
    if len(Gs) == 2:
        return Gs[0], Gs[1]
    G = Gs[0]
    return jnp.ones(G.shape[:-2] + (1, G.shape[-1]), G.dtype), G


def padded_dims(N1: int, Nr: int, k: int) -> Tuple[int, int, int]:
    """(N1p, Nrp, K): the (8, 128)-aligned shapes both engines run on
    (a one-row leading factor stays one row). Raises ValueError where a
    pick could not be indexed exactly: a grid axis above ``MAX_AXIS`` or
    N1 * Nr items beyond int32."""
    N1p = N1 if N1 == 1 else _round_up(N1, SUBLANE)
    Nrp = _round_up(Nr, LANE)
    if max(N1p, Nrp) > MAX_AXIS or N1 * Nr >= 2 ** 31:
        raise ValueError(
            f"phase-2 selection indexes the (N1, Nr) = ({N1}, {Nr}) item "
            f"grid with float32 row/column indices (exact up to "
            f"{MAX_AXIS}) and int32 items; this factorization is "
            f"outside that range")
    return N1p, Nrp, _round_up(k, SUBLANE)


def pad_pair(us: jax.Array, G1: jax.Array, Gr: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Zero-pad (us (..., k), G1 (..., N1, k), Gr (..., Nr, k)) to
    ``padded_dims`` — works on unbatched and batched stacks alike."""
    N1p, Nrp, K = padded_dims(G1.shape[-2], Gr.shape[-2], G1.shape[-1])

    def pad(x, rows):
        widths = [(0, 0)] * (x.ndim - 2) + [(0, rows - x.shape[-2]),
                                            (0, K - x.shape[-1])]
        return jnp.pad(x.astype(jnp.float32), widths)

    us = jnp.pad(us.astype(jnp.float32),
                 [(0, 0)] * (us.ndim - 1) + [(0, K - us.shape[-1])])
    return us, pad(G1, N1p), pad(Gr, Nrp)


def _dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T, f32 accumulate at full precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _shift(x: jax.Array, s: int, axis: int, roll=None) -> jax.Array:
    """``x`` moved ``s`` places toward higher indices along ``axis``,
    zero-filled: a padded slice in XLA, a masked ``roll`` in-kernel."""
    if roll is not None:
        return jnp.where(_iota(x.shape, axis) >= s, roll(x, s, axis), 0.0)
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (s, 0)
    return jnp.pad(jax.lax.slice_in_dim(x, 0, n - s, axis=axis), pad)


def _inclusive_scan(x: jax.Array, axis: int, roll=None) -> jax.Array:
    """Hillis-Steele inclusive prefix sum along ``axis`` of a 2-D array:
    log2(n) shifted adds, the same f32 adds in the same order on every
    backend."""
    s = 1
    while s < x.shape[axis]:
        x = x + _shift(x, s, axis, roll)
        s *= 2
    return x


def _fold_tiles(norms: jax.Array, roll=None) -> jax.Array:
    """(rows, T·128) -> (rows, 128): the 128-lane tiles folded in halves
    over the next power of two >= T, tile j + h onto tile j (a tile past
    the end counts as zeros). In XLA one padded slice-add per level;
    in-kernel a list of aligned tiles. The same adds either way."""
    T = norms.shape[1] // LANE
    h = 1 << max(T - 1, 0).bit_length()
    if roll is None:
        x = norms
        while h > 1:
            h //= 2
            n = x.shape[1] // LANE
            lo, hi = x[:, :min(h, n) * LANE], x[:, h * LANE:]
            x = lo + jnp.pad(hi, ((0, 0), (0, lo.shape[1] - hi.shape[1])))
        return x
    tiles = [norms[:, c * LANE:(c + 1) * LANE] for c in range(T)]
    while h > 1:
        h //= 2
        tiles = [t + tiles[j + h] if j + h < len(tiles) else t
                 for j, t in enumerate(tiles[:h])]
    return tiles[0]


def row_totals(norms: jax.Array, roll=None) -> jax.Array:
    """Per-row mass of a (rows, width) grid, (rows, 1): the lane tiles
    folded (``_fold_tiles``), then the lanes folded in halves, lane j + s
    onto lane j. ``roll=None`` slices; in-kernel the fold rolls whole
    vregs (lane j meets lane j + s either way, so the sums are
    bit-identical) and lane 0 is read out with an exact masked max."""
    x = _fold_tiles(norms, roll) if norms.shape[1] > LANE else norms
    s = x.shape[1] // 2
    while s >= 1:
        x = (x[:, :s] + x[:, s:2 * s] if roll is None
             else x + roll(x, LANE - s, 1))
        s //= 2
    if roll is None:
        return x
    return jnp.max(jnp.where(_iota(x.shape, 1) == 0, x, -jnp.inf), axis=1,
                   keepdims=True)


def _row_mass(norms: jax.Array, N1: int, Nr: int, roll=None):
    """(row totals, inclusive row prefix, exclusive row prefix, total).

    In-kernel on the whole padded grid, the prefix broadcast across 128
    lanes. The XLA engine drops what only adds exact zeros: rows past N1
    (zero mass; a prefix entry depends only on the rows above it) and,
    on a one-tile grid, the lanes past the next power of two >= Nr (fold
    steps that wide add a zero to every lane they keep)."""
    if roll is None:
        width = norms.shape[1]
        if width == LANE:
            width = min(LANE, 1 << max(Nr - 1, 0).bit_length())
        tot = rows = row_totals(norms[:N1, :width])
    else:
        tot = row_totals(norms, roll)
        rows = jnp.broadcast_to(tot, (tot.shape[0], LANE))
    if rows.shape[0] == 1:
        inc, exc = rows, jnp.zeros_like(rows)
    else:
        inc = _inclusive_scan(rows, 0, roll)
        exc = _shift(inc, 1, 0, roll)
    real_row = _iota(inc.shape, 0) < N1
    return tot, inc, exc, jnp.max(jnp.where(real_row, inc, 0.0))


def prefix_mass(norms: jax.Array, N1: int, Nr: int):
    """(total, csum): the total mass and the (N1, Nrp) row-major prefix
    mass that ``select_step`` inverts (XLA; for diagnostics — a step
    scans only the row it picks)."""
    _, _, exc, total = _row_mass(norms, N1, Nr)
    return total, exc + _inclusive_scan(norms[:N1], 1)


def _first_or_last(hit, has, idx):
    """Lowest ``idx`` where ``hit``; if none, highest where ``has``; else
    0 — exact float32 min/max reductions."""
    first = jnp.min(jnp.where(hit, idx, _NO_POS))
    last = jnp.max(jnp.where(has, idx, -1.0))
    return jnp.maximum(jnp.where(first < _NO_POS, first, last),
                       0.0).astype(jnp.int32)


def _row(x: jax.Array, i: jax.Array, roll) -> jax.Array:
    """Row ``i`` of a 2-D array, (1, n): indexed in XLA; in-kernel an
    iota-masked sum (one nonzero term per column, so exact)."""
    if roll is None:
        return jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=True)
    return jnp.sum(jnp.where(_iota(x.shape, 0) == i, x, 0.0), axis=0,
                   keepdims=True)


def select_step(t, u, g1, gr, norms, bt, *, N1: int, Nr: int, roll=None):
    """One chain-rule step on padded operands — the arithmetic both
    engines share (``roll=None``: XLA; ``pltpu.roll``: in-kernel).

    t: () int32 step; u: () f32 uniform; g1 (N1p, K); gr (Nrp, K);
    norms (N1p, Nrp) residual mass; bt (K, K) basis rows q_0..q_{t-1}.
    Returns (alive, i, norms', bt'): alive is False when the residual
    mass has collapsed (nothing may be picked), i the picked item.
    """
    tot, inc, exc, total = _row_mass(norms, N1, Nr, roll)
    r = u * total
    rowf = _iota(inc.shape, 0).astype(jnp.float32)
    real = (rowf < N1) & (tot > 0.0)
    i1 = _first_or_last(real & (inc > r), real, rowf)
    if roll is None:
        nrow = jax.lax.dynamic_slice(norms, (i1, 0), (1, Nr))
    else:
        nrow = _row(norms, i1, roll)                         # (1, Nrp)
    base = jnp.max(jnp.where(rowf == i1.astype(jnp.float32), exc, -1.0))
    csum = base + _inclusive_scan(nrow, 1, roll)
    colf = _iota(nrow.shape, 1).astype(jnp.float32)
    has = (colf < Nr) & (nrow > 0.0)
    ir = _first_or_last(has & (csum > r), has, colf)
    w = _row(g1, i1, roll) * _row(gr, ir, roll)  # (1, K) row V[i]
    q = w - _dot(_dot_nt(w, bt), bt)
    q = q - _dot(_dot_nt(q, bt), bt)             # CGS2: second pass
    qn2 = jnp.sum(q * q)
    q = jnp.where(qn2 > EPS, q / jnp.sqrt(jnp.maximum(qn2, EPS)), 0.0)
    ct = _dot_nt(g1 * q, gr)                     # (N1p, Nrp) = V q
    norms = jnp.maximum(norms - ct * ct, 0.0)
    if roll is None:
        norms = jax.lax.dynamic_update_slice(
            norms, jnp.zeros((1, 1), norms.dtype), (i1, ir))
        bt = jax.lax.dynamic_update_slice(bt, q, (t, 0))
    else:
        picked = (_iota(norms.shape, 0) == i1) & (_iota(norms.shape, 1) == ir)
        norms = jnp.where(picked, 0.0, norms)
        bt = jnp.where(_iota(bt.shape, 0) == t, q, bt)
    return total > MASS_EPS, i1 * Nr + ir, norms, bt


def init_norms(g1: jax.Array, gr: jax.Array) -> jax.Array:
    """norms[n1, nr] = sum_c G1[n1, c]^2 Gr[nr, c]^2 — the diagonal of K."""
    return _dot_nt(g1 * g1, gr * gr)


def _phase2_kernel(keff_ref, us_ref, g1_ref, gr_ref, picks_ref,
                   norms_ref, bt_ref, *, N1: int, Nr: int):
    keff = keff_ref[pl.program_id(0)]
    g1 = g1_ref[0]                               # (N1p, K)
    gr = gr_ref[0]                               # (Nrp, K)
    us = us_ref[0]                               # (1, K)
    lanes = _iota(us.shape, 1)
    norms_ref[...] = init_norms(g1, gr)
    bt_ref[...] = jnp.zeros(bt_ref.shape, jnp.float32)
    picks_ref[0] = jnp.full(us.shape, -1, jnp.int32)

    def cond(carry):
        t, alive = carry
        return (t < keff) & (alive > 0)

    def body(carry):
        t, _ = carry
        u = jnp.sum(jnp.where(lanes == t, us, 0.0))
        alive, i, norms, bt = select_step(t, u, g1, gr, norms_ref[...],
                                          bt_ref[...], N1=N1, Nr=Nr,
                                          roll=pltpu.roll)

        @pl.when(alive)
        def _commit():
            norms_ref[...] = norms
            bt_ref[...] = bt
            picks_ref[0] = jnp.where(lanes == t, i, picks_ref[0])

        return t + 1, alive.astype(jnp.int32)

    jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(1)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def phase2_select_pallas(us: jax.Array, k_eff: jax.Array,
                         G1: jax.Array, Gr: jax.Array,
                         interpret: bool = False) -> jax.Array:
    """Fused batched phase-2 selection off a canonical factor pair.

    us:    (B, k_max) per-step uniforms.
    k_eff: (B,) int32 — live step count per sample (<= k_max).
    G1:    (B, N1, k_max) leading factor columns.
    Gr:    (B, Nr, k_max) trailing-factor fold (``canonical_pair``).
    Returns (B, k_max) int32 picks, -1 in padded/dead slots.
    """
    B, k_max = us.shape
    N1, Nr = G1.shape[1], Gr.shape[1]
    us, G1, Gr = pad_pair(us, G1, Gr)
    N1p, Nrp, K = G1.shape[1], Gr.shape[1], G1.shape[2]
    kern = functools.partial(_phase2_kernel, N1=N1, Nr=Nr)
    picks = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, K), lambda b, ke: (b, 0, 0)),
                pl.BlockSpec((1, N1p, K), lambda b, ke: (b, 0, 0)),
                pl.BlockSpec((1, Nrp, K), lambda b, ke: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, K), lambda b, ke: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((N1p, Nrp), jnp.float32),   # residual norms
                pltpu.VMEM((K, K), jnp.float32),       # basis rows
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, K), jnp.int32),
        interpret=interpret,
        # the kernel's name in the lowered program and the device trace
        name="phase2_select_pallas",
    )(k_eff.astype(jnp.int32), us.reshape(B, 1, K), G1, Gr)
    return picks[:, 0, :k_max]
