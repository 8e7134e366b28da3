"""Pallas TPU kernel: exact backward through the sig-kernel PDE (pySigLib §3.4).

One reverse wavefront pass per strip computes the adjoint

    g[a,b] = g[a,b+1]·A(Δ[a−1,b]) + g[a+1,b]·A(Δ[a,b−1]) − g[a+1,b+1]·B(Δ[a,b])

and accumulates   dΔ[i,j] += g[i+1,j+1]·[(k̂[i+1,j]+k̂[i,j+1])·A'(Δ) − k̂[i,j]·B'(Δ)]

folding refined cells back onto the unrefined Δ block.  Strips are processed
bottom-up (grid index maps reverse the strip order).  Every adjoint wavefront
row is stored at its sublane of a (W, T) scratch; the strip above reads lanes
0/1 of the rows of the strip below from it and overwrites them in place
(reads trail writes — the mirror image of the forward trick).  k̂ inside the
strip is RECOMPUTED from the forward's checkpoint — O(nx·ny/T) saved state
instead of the full grid, a beyond-paper improvement (the paper stores the
full grid / recomputes fully).  The skewed dΔ is unskewed by the forward's
row shear (rows stored in reverse order so the shear is a right rotation)
and folded back onto the unrefined block by 0/1 matmuls.

Like the fused forward, the backward packs P pairs into each program
(``bwd_pack``: ``PACK`` = 16, fewer for long paths and small batches):
grid = (batch/P, n_strips), and every wavefront row of both the recompute
sweep and the adjoint sweep is a (P, T) block, one pair per sublane, in
(W·P, T) scratches whose rows t·P … t·P + P − 1 hold skew-step t of the P
pairs.  Δ, the checkpoint and dΔ are refined, skewed, sheared and folded
pair by pair in a loop; each pair's dΔ is bitwise that of P = 1.

Skew/lane conventions match ``kernel.py``:
cell (r, c) := refined update (i, j) = (strip_top + r, c), value k̂[i+1, c+1],
living at skew-step t = r + c, lane r.

Per-scheme adjoints (derivations in ``stencil.py``; this kernel recomputes
with the SAME stencil the forward used).  The order-2 stencil's skew reads
make cells (a−1, b+1) and (a+1, b−1) additional readers of k̂[a,b], so its
adjoint gains two −C terms::

    g[a,b] = g[a,b+1]·A(Δ[a−1,b]) + g[a+1,b]·A(Δ[a,b−1]) − g[a+1,b+1]·B₂(Δ[a,b])
             − g[a,b+2]·C(Δ[a−1,b+1]) − g[a+2,b]·C(Δ[a+1,b−1])

In lane terms the extra readers are G(r, c+2) (same lane, skew t+2 — the
``gnext2`` carry unshifted) and G(r+2, c) (two lanes down): lane T−2's reaches
row 0 of the strip below and lane T−1's reaches row 1 of the strip below
(lanes 0/1 of its stored adjoint rows), with coefficients from that strip's
first two refined Δ rows.  The dΔ accumulation gains
``− (k̂[i+1,j−1] + k̂[i−1,j+1])·C'(Δ)``; the skew k̂ reads come from the
recomputed strip (``ksk`` two skew-steps back) with lanes 1/0 falling back to
lanes T−1/T−2 of the checkpoint.
Boundary skew reads were the constant 1 in the forward and carry no adjoint.
``interior_dtype="bfloat16"`` recomputes k̂ with the forward's rounding but
keeps every adjoint quantity f32 (straight-through gradient — see stencil.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import KERNEL_NAMES
from . import stencil
from .kernel import (LANES, check_pack, check_strip, compiler_params,
                     cps_lanes, expander, fused_pack, mm, pack_block,
                     pack_lanes, pair_rows, refine, roll, shear, skew,
                     step_rows, strip_width, sweep, vmem_scratch, zeros_row)

#: skew rows (pairs × W) a backward pack may hold.  Its six (W·P, T)
#: scratches take 3 KiB a row, so 8,192 rows hold 24 MiB and the VMEM
#: request (``vmem_limit(W, T, 6, P)``) stays under 56 MiB: all 16 pairs
#: up to W = 512, fewer for longer paths, one pair past W = 4,096
BWD_PACK_ROWS = 16 * 512


def bwd_pack(ny: int, T: int, batch: int) -> int:
    """Pairs per program of the exact backward: ``fused_pack``'s rule
    under the backward's row budget."""
    return fused_pack(ny, T, batch, BWD_PACK_ROWS)


def per_pair(P: int, body) -> None:
    """``body(j)`` for each pair j < P of a pack, in a loop: the program
    holds one copy of the per-pair work whatever P is."""
    if P == 1:
        body(0)
    else:
        pl.loop(0, P)(body)


def bwd_kernel(delta_ref, delta_next_ref, cps_ref, gbar_ref, ddelta_ref,
               s_ref, above_ref, ksk_ref, dn_ref, gst_ref, dsk_ref, *,
               T: int, lam1: int, lam2: int, ny: int,
               scheme: str = "order1", interior_dtype: str = "float32"):
    """One (pack, reversed-strip) grid step of the exact backward pass.

    A program solves the P pairs of a pack, one per sublane of every (P, T)
    wavefront row, as ``kernel.sweep`` does.
    delta_ref / delta_next_ref: (P, R, Ly) Δ of this strip / the strip below.
    cps_ref:  (P, 1, CR, W) the forward's checkpoints of this strip.
    gbar_ref: the (1, 128) block of the upstream cotangent row that holds
              the pack's P lanes.
    ddelta_ref: (P, R, Ly) dΔ of this strip.
    Scratch, all (W·P, T), rows t·P … t·P + P − 1 holding skew-step t of
    the P pairs: skewed Δ, rows of the strip above (from the checkpoint),
    recomputed k̂ rows, the strip below's first two refined Δ rows as
    columns, adjoint rows (carried between strips), skewed dΔ.  Δ, the
    checkpoint and dΔ are moved pair by pair, each to its strided rows.
    """
    b, s_rev = pl.program_id(0), pl.program_id(1)
    P, _, Ly = delta_ref.shape
    W = strip_width(ny, T)
    CR = cps_ref.shape[2]
    n_steps = ny + T - 1
    order2 = scheme == "order2"
    m1, m2 = (1 << lam1) - 1, (1 << lam2) - 1

    @pl.when(s_rev == 0)
    def _reset():
        gst_ref[...] = jnp.zeros_like(gst_ref)

    def prologue(j):
        mine = pair_rows(j, P, W)
        s_ref[mine] = skew(refine(delta_ref[j], T, W, lam1, lam2))
        tail = cps_ref[j, 0]                                # (CR, W)
        if CR < T:
            tail = jnp.concatenate([jnp.zeros((T - CR, W), jnp.float32),
                                    tail])
        above_ref[mine] = tail.T
        # dn[c, 0] / dn[c, 1]: refined rows 0 / 1 of the strip below at
        # column c
        nxt = refine(delta_next_ref[j], T, W, lam1, lam2)
        row = jax.lax.broadcasted_iota(jnp.int32, nxt.shape, 0)
        dn_ref[mine] = jnp.where(row < 2, nxt, 0.0).T

    per_pair(P, prologue)
    dsk_ref[...] = jnp.zeros_like(dsk_ref)

    # ---- phase 1: recompute strip interior k̂ from the checkpoint -----------
    sweep(s_ref, above_ref, ksk_ref, T=T, lam1=lam1, lam2=lam2, ny=ny,
          scheme=scheme, interior_dtype=interior_dtype)

    # ---- phase 2: reverse adjoint wavefront --------------------------------
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, T), 1)
    # each sublane's cotangent: lane off + j of the pack's (1, 128) block
    _, off = pack_block(b, P)
    pos = jax.lax.broadcasted_iota(jnp.int32, (P, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (P, LANES), 0)
    gbar = jnp.sum(jnp.where(pos == off + sub, gbar_ref[...], 0.0), axis=1,
                   keepdims=True)                           # (P, 1)

    def rows(ref, i):
        return ref[step_rows(i, P), :]

    def bstep(i, carry):
        t = n_steps - 1 - i
        gnext, gnext2, g_q2 = carry                         # G at skew t+1, t+2
        # lane T−1 sits in column t − T + 1 (q); lanes 0/1 of row q of the
        # strip below hold its row-0/row-1 values at that column.  Row q2 =
        # q + 1 was this step's predecessor's row q: carried, because this
        # strip has already overwritten it when T = 1.
        q = jnp.maximum(t - (T - 1), 0)
        q2 = jnp.maximum(t - (T - 2), 0)
        g_q = rows(gst_ref, q)
        x_q, x_q2 = rows(dn_ref, q), rows(dn_ref, q2)

        p_c = rows(s_ref, t)                                # Δ(r, c)
        p_a = rows(s_ref, t + 1)                            # Δ(r, c+1)
        p_t2 = rows(s_ref, t + 2)                           # Δ(r, c+2)
        last = lane == T - 1
        p_r1 = jnp.where(last, roll(x_q, -1), roll(p_a, -1))      # Δ(r+1, c)
        p_r1c1 = jnp.where(last, roll(x_q2, -1), roll(p_t2, -1))  # Δ(r+1, c+1)

        g_right = gnext                                     # G(r, c+1)
        g_down = jnp.where(last, roll(g_q, -1), roll(gnext, -1))  # G(r+1, c)
        g_downright = jnp.where(last, roll(g_q2, -1),             # G(r+1, c+1)
                                roll(gnext2, -1))

        if order2:
            # extra readers of k̂[a,b]: the cells whose skew neighbour it was
            p_r2 = jnp.where(lane == T - 2, roll(x_q2, -2),       # Δ(r+2, c)
                             jnp.where(last, roll(x_q, -2), roll(p_t2, -2)))
            g_right2 = gnext2                               # G(r, c+2)
            g_down2 = jnp.where(lane >= T - 2, roll(g_q2, -2),    # G(r+2, c)
                                roll(gnext2, -2))
            # per-WRITER gridline fallback (stencil.py): writer cells are
            # (r+1, c+1) for the −B term, (r, c+2) / (r+2, c) for the −C
            # terms; global row ≡ lane row (mod 2^λ1) because T is a
            # multiple of 2^λ1, so the masks hold across strip boundaries
            col = t - lane
            edge_b = (((lane + 1) & m1) == 0) | (((col + 1) & m2) == 0)
            edge_cr = ((lane & m1) == 0) | (((col + 2) & m2) == 0)
            edge_cd = (((lane + 2) & m1) == 0) | ((col & m2) == 0)
            cur = (g_right * stencil.coeff_A(p_a)
                   + g_down * stencil.coeff_A(p_r1)
                   - g_downright * stencil.coeff_B2_at(p_r1c1, edge_b)
                   - g_right2 * stencil.coeff_C2_at(p_t2, edge_cr)
                   - g_down2 * stencil.coeff_C2_at(p_r2, edge_cd))
        else:
            cur = (g_right * stencil.coeff_A(p_a)
                   + g_down * stencil.coeff_A(p_r1)
                   - g_downright * stencil.coeff_B1(p_r1c1))
        # seed ∂F/∂k̂[nx, ny] at the bottom-right cell of the bottom strip
        seed_here = (s_rev == 0) & (t == n_steps - 1)
        cur = cur + jnp.where(seed_here & last, gbar, 0.0)
        active = (lane <= t) & (lane > t - ny)
        cur = jnp.where(active, cur, 0.0)

        # ---- dΔ contribution of cells on this anti-diagonal ----
        k_tm1 = rows(ksk_ref, jnp.maximum(t - 1, 0))
        k_tm2 = rows(ksk_ref, jnp.maximum(t - 2, 0))
        a_1 = rows(above_ref, jnp.minimum(t + T - 1, W - 1))
        a_2 = rows(above_ref, jnp.clip(t + T - 2, 0, W - 1))
        k_left = jnp.where(lane == t, 1.0, k_tm1)               # k̂[i+1, j]
        k_up = jnp.where(lane == 0, roll(a_1, 1), roll(k_tm1, 1))  # k̂[i, j+1]
        k_upleft = jnp.where(lane == 0, roll(a_2, 1), roll(k_tm2, 1))
        k_upleft = jnp.where(lane == t, 1.0, k_upleft)          # k̂[i, j]
        if order2:
            k_dl = jnp.where(lane >= t - 1, 1.0, k_tm2)         # k̂[i+1, j-1]
            k_ul = jnp.where(lane < 2, roll(a_2, 2), roll(k_tm2, 2))
            # dΔ selects on the contributing cell (r, c) itself
            edge_cell = ((lane & m1) == 0) | (((t - lane) & m2) == 0)
            contrib = cur * ((k_left + k_up) * stencil.coeff_dA(p_c)
                             - k_upleft * stencil.coeff_dB2_at(p_c, edge_cell)
                             - (k_dl + k_ul)
                             * stencil.coeff_dC2_at(p_c, edge_cell))
        else:
            contrib = cur * ((k_left + k_up) * stencil.coeff_dA(p_c)
                             - k_upleft * stencil.coeff_dB1(p_c))
        contrib = jnp.where(active, contrib, 0.0)
        dsk_ref[step_rows(W - 1 - t, P), :] = contrib      # reversed rows
        gst_ref[step_rows(t, P), :] = cur
        return (cur, gnext, g_q)

    zeros = zeros_row(s_ref, P)
    jax.lax.fori_loop(0, n_steps, bstep, (zeros, zeros, rows(gst_ref, ny)))

    # ---- phase 3: unskew + dyadic fold -> unrefined dΔ block ----------------
    # dsk[W−1−t, r] holds cell (r, t − r): rotating lane-row r right by r
    # puts cell (r, c) at lane W−1−c, which the reversed expander folds.
    fold_y = expander(W, Ly, lam2, reverse=True)

    def epilogue(j):
        dM = mm(shear(dsk_ref[pair_rows(j, P, W)].T), fold_y)
        if lam1:
            dM = mm(expander(T, T >> lam1, lam1, transpose=True), dM)
        ddelta_ref[j] = dM * 2.0 ** (-(lam1 + lam2))

    per_pair(P, epilogue)


def build_bwd(batch: int, Lx: int, Ly: int, *, T: int, lam1: int, lam2: int,
              interpret: bool, scheme: str = "order1",
              interior_dtype: str = "float32", pack: int | None = None):
    """Exact backward: ``f(delta, delta, cps, gbar (batch,)) -> dΔ`` of
    shape (batch, Lx, Ly); Δ is passed twice (this strip / the strip below).

    Grid (batch/P, n_strips), strips reversed: each program solves a pack
    of P pairs, one per sublane.  P is ``bwd_pack``'s unless ``pack`` says
    otherwise; batch must be a multiple of P (ops.py pads it).
    """
    R = check_strip(T, lam1, Lx, scheme)
    n_strips = Lx // R
    ny = Ly << lam2
    W = strip_width(ny, T)
    CR = cps_lanes(T)
    pack = pack or bwd_pack(ny, T, batch)
    n_packs = check_pack(batch, pack)
    kern = functools.partial(bwd_kernel, T=T, lam1=lam1, lam2=lam2, ny=ny,
                             scheme=scheme, interior_dtype=interior_dtype)

    def rev(s):
        return n_strips - 1 - s

    call = pl.pallas_call(
        kern,
        grid=(n_packs, n_strips),
        in_specs=[
            pl.BlockSpec((pack, R, Ly), lambda b, s: (b, rev(s), 0)),
            pl.BlockSpec((pack, R, Ly),
                         lambda b, s: (b, jnp.minimum(rev(s) + 1, n_strips - 1), 0)),
            pl.BlockSpec((pack, 1, CR, W), lambda b, s: (b, rev(s), 0, 0)),
            pl.BlockSpec((1, LANES),
                         lambda b, s: (0, pack_block(b, pack)[0])),
        ],
        out_specs=pl.BlockSpec((pack, R, Ly), lambda b, s: (b, rev(s), 0)),
        out_shape=jax.ShapeDtypeStruct((batch, Lx, Ly), jnp.float32),
        scratch_shapes=[vmem_scratch((W * pack, T)) for _ in range(6)],
        compiler_params=compiler_params(W, T, 6, pack),
        interpret=interpret,
        name=KERNEL_NAMES["bwd"],
    )

    def run(delta, delta_next, cps, gbar):
        row = jnp.pad(gbar, (0, pack_lanes(batch) - batch))
        return call(delta, delta_next, cps, row.reshape(1, -1))

    return run
