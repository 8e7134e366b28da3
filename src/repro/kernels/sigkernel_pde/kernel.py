"""Pallas TPU kernel: Goursat-PDE signature-kernel solver (pySigLib §3.3).

TPU-native translation of the paper's GPU wavefront scheme:

* the PDE grid is swept in **row strips of T refined rows** (T ≤ 128 lanes)
  — the analogue of the paper's 32-thread blocks;
* inside a strip the anti-diagonal wavefront advances one skew-step per loop
  iteration: step t computes the (1, T) row of cells (r, c = t − r), lane r
  ((P, T) in the fused kernels, below),
  carrying the two previous rows (``prev``, ``prev2``) — the analogue of the
  paper's 3 rotating anti-diagonals in CUDA shared memory;
* every wavefront row is stored in a ``(W, T)`` VMEM scratch at sublane t.
  The next strip reads the rows of the strip above from the same scratch
  (lane T−1 of row t + T − 1 is k̂[strip_top, t + 1], moved to lane 0 by a
  lane roll) and overwrites them in place — reads lead writes by T − 1
  rows, the paper's trick of reusing the initial-condition vector between
  blocks.  All accesses are whole rows at a dynamic sublane: no scalar ever
  moves between VMEM and the vector unit;
* dyadic refinement, zero padding to W lanes and the skew are built in VMEM
  from the unrefined (R, Ly) block, R = T / 2^λ1: 0/1 expansion matrices
  (exact under ``Precision.HIGHEST``) refine and pad, and one strided lane
  roll followed by a transpose skews, S[t, r] = Δ_refined(r, t − r).  The
  refined Δ never exists in HBM;
* Δ itself is precomputed OUTSIDE the kernel by one batched MXU matmul
  (paper design choice (2)) — see ``ops.py`` — or, in the fused variants,
  recomputed per strip from the increments.

Grid = (batch, n_strips); TPU grid iteration is sequential per core, so VMEM
scratch persists across strips — the TPU-native replacement for CUDA
inter-block synchronisation.  Kernel values are written as lanes of one
resident lane-dense output row.

The fused forward kernels pack P pairs into each program (``fused_pack``:
``PACK`` = 16, fewer for long paths and small batches): grid = (batch/P,
n_strips), or (Bx, By/P, n_strips) for the Gram, and every wavefront row
is a (P, T) block, one pair per sublane.  The scratches are (W·P, T), rows
t·P … t·P + P − 1 holding skew-step t of the P pairs, so the sweep loads,
computes and stores whole vregs at sublane-aligned rows; Δ and its skew
are still built pair by pair.  Every pair of a call shares ny, T and λ, so
the lane masks are the one-pair program's, and each pair's result is
bitwise that of P = 1.  Each program writes its P values to P lanes of
the lane-dense output row, in the (1, 128) block its pack falls in.

In grad mode the kernel additionally emits one **checkpoint** per strip:
lanes T−CR … T−1 (CR = min(T, 8)) of the stored rows of the strip above,
transposed into a lane-dense (CR, W) tile.  Lane T−1 holds the strip's top
boundary row and lane T−2 the row above it (the order-2 stencil's extra
skew read).  The backward kernel rebuilds the strip interior from it —
O(nx·ny / T) activation memory instead of the full grid, a beyond-paper
improvement (the paper stores the full grid).

Scheme support (``GridConfig.scheme`` — coefficient sets in ``stencil.py``):
the ``"order2"`` stencil reads the two anti-diagonal neighbours
k̂_{i+1,j−1} / k̂_{i−1,j+1}, both on ``prev2`` (same lane / two lanes up);
lanes 0/1 of k̂_{i−1,j+1} come from lanes T−2/T−1 of the strip above, so
results are independent of the strip height — order-2 requires T ≥ 2.
``GridConfig.interior_dtype = "bfloat16"`` rounds every freshly computed
cell through bf16 (``stencil.round_interior``) while the strip boundaries
and the readout stay f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import KERNEL_NAMES
from . import stencil

LANES = 128
#: checkpoint lanes kept per strip (an aligned sublane tile once transposed)
CPS_LANES = 8
#: pairs per program of the fused forward kernels, one per sublane: a
#: wavefront row is two (8, 128) f32 vregs, two independent chains per step
PACK = 16
#: skew rows (pairs × W) a pack may hold: all 16 pairs up to W = 384,
#: fewer for longer paths, whose unrolled Δ build grows the program and
#: its compile time with P·W
PACK_ROWS = 16 * 384


def strip_width(ny: int, T: int) -> int:
    """Sublanes W of the skewed strip buffers: ≥ ny + T + 1, lane-aligned.

    The skew roll wraps nothing but zeros once W ≥ ny + T − 1, and the
    backward reads Δ up to skew-step ny + T.
    """
    return -(-(ny + T + 1) // LANES) * LANES


def fused_pack(ny: int, T: int, batch: int, rows: int = PACK_ROWS) -> int:
    """Pairs per program of the fused kernels: ``PACK``, halved while the
    pack's skew rows P·W pass ``rows`` or half the pack would hold all
    ``batch`` pairs (a lone pair keeps the one-pair program)."""
    W = strip_width(ny, T)
    P = PACK
    while P > 1 and (P * W > rows or P >= 2 * batch):
        P //= 2
    return P


def cps_lanes(T: int) -> int:
    return min(T, CPS_LANES)


def vmem_limit(W: int, T: int, n_buffers: int, pack: int = 1) -> int:
    """Scoped-VMEM request: ``n_buffers`` (W, T) scratches plus as many
    (T, W) temporaries, lanes padded to 128, doubled for headroom; packed
    scratches, (W·P, T), add their P − 1 further pairs on top."""
    row_bytes = 4 * W * max(T, LANES)
    est = row_bytes * (2 * n_buffers)
    base = min(96 * 2 ** 20, max(32 * 2 ** 20, est))
    return base + row_bytes * n_buffers * (pack - 1)


def compiler_params(W: int, T: int, n_buffers: int, pack: int = 1):
    return pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit(W, T, n_buffers, pack))


def roll(x: jax.Array, shift: int) -> jax.Array:
    """Lane rotation with ``jnp.roll`` semantics (x[i] -> out[i + shift])."""
    shift %= x.shape[-1]
    return pltpu.roll(x, shift, x.ndim - 1) if shift else x


def expander(n_fine: int, n_coarse: int, lam: int, *,
             transpose: bool = False, reverse: bool = False) -> jax.Array:
    """0/1 dyadic expansion matrix E[i, j] = [i >> lam == j], (n_fine,
    n_coarse); fine indices past n_coarse << lam get a zero row (padding).
    ``reverse`` counts i from the end; ``transpose`` returns Eᵀ."""
    shape = (n_coarse, n_fine) if transpose else (n_fine, n_coarse)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transpose else 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transpose else 1)
    if reverse:
        i = n_fine - 1 - i
    return ((i >> lam) == j).astype(jnp.float32)


def mm(a: jax.Array, b: jax.Array, *, nt: bool = False) -> jax.Array:
    """f32 matmul a @ b (a @ bᵀ when ``nt``) at full precision."""
    dims = (((1,), (1,) if nt else (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def refine(blk: jax.Array, T: int, W: int, lam1: int, lam2: int) -> jax.Array:
    """Unrefined Δ block (R, Ly) -> refined, zero-padded (T, W) block."""
    R, Ly = blk.shape
    if lam1:
        blk = mm(expander(T, R, lam1), blk)
    return mm(blk, expander(W, Ly, lam2), nt=True) * 2.0 ** (-(lam1 + lam2))


def refine_fused(dx: jax.Array, dy: jax.Array, T: int, W: int, lam1: int,
                 lam2: int) -> jax.Array:
    """Refined, zero-padded Δ block (T, W) straight from increments: the
    (R, d) × (d, Ly) MXU matmul with both sides dyadically expanded first."""
    if lam1:
        dx = mm(expander(T, dx.shape[0], lam1), dx)
    dy = mm(expander(W, dy.shape[0], lam2), dy)
    return mm(dx, dy, nt=True) * 2.0 ** (-(lam1 + lam2))


def shear(M: jax.Array) -> jax.Array:
    """Rotate row r of M right by r lanes.

    A barrel shifter: log2(T) lane rotations, each applied to the rows with
    that bit of r set.  (Interpret mode would unroll a strided rotation
    into T pieces per call.)
    """
    row = jax.lax.broadcasted_iota(jnp.int32, M.shape, 0)
    bit = 1
    while bit < M.shape[0]:
        M = jnp.where((row & bit) != 0, roll(M, bit), M)
        bit <<= 1
    return M


def skew(M: jax.Array) -> jax.Array:
    """(T, W) -> (W, T) with S[t, r] = M[r, t − r] (0 where t < r)."""
    return shear(M).T


def step_rows(i, P: int):
    """Rows i·P … i·P + P − 1 of a (W·P, T) scratch, the P pairs'
    skew-step i: a sublane-aligned slice."""
    return pl.ds(i, 1) if P == 1 else pl.ds(pl.multiple_of(i * P, P), P)


def pair_rows(j, P: int, W: int):
    """Index of pair j's rows in a (W·P, T) scratch: every P-th row from
    row j (all of them when P = 1)."""
    return ... if P == 1 else (pl.ds(j, W, stride=P), slice(None))


def sweep(s_ref, src_ref, dst_ref, *, T, lam1, lam2, ny, scheme="order1",
          interior_dtype="float32"):
    """Anti-diagonal sweep of one strip of P pairs at once.

    P is read from the scratch shapes, (W·P, T) for W = ``strip_width(ny,
    T)``: rows t·P … t·P + P − 1 hold skew-step t of the P pairs, so a
    wavefront step is one (P, T) row, a pair per sublane.  Every pair of a
    call shares ny, T and λ, so the lane masks broadcast over the pairs.

    s_ref:   (W·P, T) skewed refined Δ of the strip.
    src_ref: (W·P, T) wavefront rows of the strip above (ones above the
             first strip); only lanes T−1 and T−2 are read.
    dst_ref: (W·P, T) receives this strip's wavefront rows; may be
             ``src_ref`` (reads lead writes).
    Returns the last (P, T) row, whose lane T−1 is k̂[strip_bottom, ny] of
    each pair.
    """
    W = strip_width(ny, T)
    P = s_ref.shape[0] // W
    order2 = scheme == "order2"
    m1, m2 = (1 << lam1) - 1, (1 << lam2) - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, T), 1)

    def above(i):
        # lane T−1 of row i is k̂[strip_top, i − T + 2] and lane T−2 is
        # k̂[strip_top − 1, i − T + 3]; rows past the end only feed
        # inactive lanes
        return src_ref[step_rows(jnp.minimum(i, W - 1), P), :]

    def step(t, carry):
        prev, prev2, above_prev = carry                # (P, T) f32
        above_t = above(t + T - 1)
        p = s_ref[step_rows(t, P), :]                  # anti-diagonal of Δ
        shift_prev = jnp.where(lane == 0, roll(above_t, 1), roll(prev, 1))
        shift_prev2 = jnp.where(lane == 0, roll(above_prev, 1),
                                roll(prev2, 1))
        left = jnp.where(lane == t, 1.0, prev)
        upleft = jnp.where(lane == t, 1.0, shift_prev2)
        if order2:
            # Skew neighbours both sit two wavefront steps back (prev2):
            # k_dl = k̂[i+1, c−1] is prev2 at the SAME lane (:= 1 for c ≤ 1 —
            # the boundary of ones extends); k_ul = k̂[i−1, c+1] is prev2 two
            # lanes up, lanes 1/0 reading lanes T−1/T−2 of the strip above.
            # Data-gridline fallback (stencil.py): global row = strip·T +
            # lane and T ≡ 0 (mod 2^λ1), so the row test is lane mod 2^λ1;
            # the column is c = t − lane.
            edge = ((lane & m1) == 0) | (((t - lane) & m2) == 0)
            k_dl = jnp.where(lane >= t - 1, 1.0, prev2)
            k_ul = jnp.where(lane < 2, roll(above_prev, 2), roll(prev2, 2))
            cur = ((left + shift_prev) * stencil.coeff_A(p)
                   - upleft * stencil.coeff_B2_at(p, edge)
                   - (k_dl + k_ul) * stencil.coeff_C2_at(p, edge))
        else:
            cur = ((left + shift_prev) * stencil.coeff_A(p)
                   - upleft * stencil.coeff_B1(p))
        cur = stencil.round_interior(cur, interior_dtype)
        active = (lane <= t) & (lane > t - ny)
        cur = jnp.where(active, cur, 0.0)
        dst_ref[step_rows(t, P), :] = cur
        return (cur, prev, above_t)

    zeros = zeros_row(s_ref, P)
    last, _, _ = jax.lax.fori_loop(0, ny + T - 1, step,
                                   (zeros, zeros, above(max(T - 2, 0))))
    return last


def zeros_row(ref, P: int = 1) -> jax.Array:
    """A (P, T) row of zeros computed from rows 0 … P − 1 of ``ref``
    (finite).

    Mosaic lays a loop carry out like its initial value; a constant's
    replicated layout cannot take the computed rows the loop yields.
    """
    return ref[pl.ds(0, P), :] * 0.0 + 0.0


def readout(last: jax.Array) -> jax.Array:
    """(P, T) last wavefront row -> (P, 1) column of its lanes T−1."""
    T = last.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, last.shape, 1)
    return jnp.sum(jnp.where(lane == T - 1, last, 0.0), axis=1, keepdims=True)


def place(row: jax.Array, idx, last: jax.Array) -> jax.Array:
    """Lane-dense output row with lane ``idx`` := lane T−1 of ``last``."""
    k = readout(last)
    pos = jax.lax.broadcasted_iota(jnp.int32, row.shape, row.ndim - 1)
    return jnp.where(pos == idx, k, row)


def place_pack(row: jax.Array, off, last: jax.Array) -> jax.Array:
    """(1, 128) output row with lanes off … off + P − 1 := lanes T−1 of
    the P rows of ``last``: each sublane's value moves to its lane by one
    select and a sum over the sublanes, in which it is the only term."""
    k = readout(last)                                  # (P, 1)
    shape = (k.shape[0], row.shape[-1])
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    vals = jnp.sum(jnp.where(pos == off + sub, k, 0.0), axis=0,
                   keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    mine = (lane >= off) & (lane < off + k.shape[0])
    return jnp.where(mine, vals, row)


def pack_lanes(batch: int) -> int:
    """Lanes of a packed kernel's output row: ``batch`` rounded up to whole
    (1, 128) blocks, so each pack writes one vreg of a lane-dense row."""
    return -(-batch // LANES) * LANES


def pack_block(b, P: int):
    """(output block, lane offset) of pack ``b`` in its row."""
    per_block = LANES // P
    return b // per_block, (b % per_block) * P


def _strip(block, P, s_ref, rows_ref, cps_ref, *, strip_axis, **kw):
    """Shared strip body: reset at the first strip, checkpoint, skew the
    (T, W) Δ block ``block(j)`` of each pair j < P into its rows, sweep."""
    @pl.when(pl.program_id(strip_axis) == 0)
    def _reset():
        rows_ref[...] = jnp.ones_like(rows_ref)

    if cps_ref is not None:
        CR = cps_ref.shape[2]
        cps_ref[0, 0] = rows_ref[...].T[-CR:]
    W = s_ref.shape[0] // P
    for j in range(P):
        s_ref[pair_rows(j, P, W)] = skew(block(j))
    return sweep(s_ref, rows_ref, rows_ref, **kw)


def fwd_kernel(delta_ref, out_ref, *refs, T: int, lam1: int, lam2: int,
               ny: int, save_cps: bool, scheme: str = "order1",
               interior_dtype: str = "float32"):
    """One (batch, strip) grid step of the forward wavefront solver.

    delta_ref: (1, R, Ly) unrefined Δ rows of this strip (VMEM block).
    out_ref:   (1, batch) resident row of final kernel values k̂[nx, ny];
               lane b is rewritten every strip, the last strip's write is
               the result.
    cps_ref:   (1, 1, CR, W) checkpoint of this strip (grad mode only).
    s_ref, rows_ref: (W, T) scratch — skewed Δ, and the wavefront rows
               carried from strip to strip.
    """
    cps_ref, s_ref, rows_ref = refs if save_cps else (None, *refs)
    M = refine(delta_ref[0], T, s_ref.shape[0], lam1, lam2)
    last = _strip(lambda _: M, 1, s_ref, rows_ref, cps_ref, strip_axis=1,
                  T=T, lam1=lam1, lam2=lam2, ny=ny, scheme=scheme,
                  interior_dtype=interior_dtype)
    out_ref[...] = place(out_ref[...], pl.program_id(0), last)


def fused_fwd_kernel(dx_ref, dy_ref, out_ref, s_ref, rows_ref, *, T: int,
                     lam1: int, lam2: int, ny: int, scheme: str = "order1",
                     interior_dtype: str = "float32"):
    """Fused-Δ forward: program (pack, strip) solves the P pairs of a pack.

    dx_ref: (P, R, d) and dy_ref: (P, Ly, d) increments of the pack; each
    pair's Δ block is computed in VMEM (an (R, d) × (d, Ly) MXU matmul) —
    Δ never exists in HBM.  out_ref: the (1, 128) block of the output row
    that holds the pack's P lanes, rewritten every strip; the last strip's
    write is the result.

    Beyond-paper variant: pySigLib precomputes Δ with one bmm (design
    choice (2)).  Whether recomputing Δ beats streaming it from HBM on a
    TPU is not measured.
    """
    W = strip_width(ny, T)
    last = _strip(
        lambda j: refine_fused(dx_ref[j], dy_ref[j], T, W, lam1, lam2),
        dx_ref.shape[0], s_ref, rows_ref, None, strip_axis=1, T=T,
        lam1=lam1, lam2=lam2, ny=ny, scheme=scheme,
        interior_dtype=interior_dtype)
    _, off = pack_block(pl.program_id(0), dx_ref.shape[0])
    out_ref[...] = place_pack(out_ref[...], off, last)


def fused_gram_kernel(dx_ref, dy_ref, out_ref, s_ref, rows_ref, *, T: int,
                      lam1: int, lam2: int, ny: int, scheme: str = "order1",
                      interior_dtype: str = "float32"):
    """Fused-Δ Gram: program (a, pack, strip) solves pairs (x_a, y_b) for
    the P columns b of the pack; dx_ref is (1, R, d), dy_ref (P, Ly, d) and
    out_ref the (1, 1, 128) block of Gram row a that holds the pack's
    lanes."""
    W = strip_width(ny, T)
    last = _strip(
        lambda j: refine_fused(dx_ref[0], dy_ref[j], T, W, lam1, lam2),
        dy_ref.shape[0], s_ref, rows_ref, None, strip_axis=2, T=T,
        lam1=lam1, lam2=lam2, ny=ny, scheme=scheme,
        interior_dtype=interior_dtype)
    _, off = pack_block(pl.program_id(1), dy_ref.shape[0])
    out_ref[0] = place_pack(out_ref[0], off, last)


def check_strip(T: int, lam1: int, Lx: int, scheme: str = "order1") -> int:
    """Validate strip geometry; return R = T >> lam1 (unrefined rows/strip).

    Raises ValueError (not a bare assert) naming the offending shape and the
    LaunchConfig knob that lifts the limit.
    """
    R = T >> lam1
    if R < 1 or R << lam1 != T:
        raise ValueError(
            f"Goursat strip height T={T} must be a power-of-two multiple of "
            f"the dyadic refinement 2**lam1={1 << lam1} — raise "
            f"LaunchConfig.pde_strip (or lower lam1); the default cap is "
            f"{LANES}")
    if Lx % R != 0:
        raise ValueError(
            f"Lx={Lx} rows are not a multiple of the R={R} unrefined rows "
            f"per strip (T={T}, lam1={lam1}) — the ops.py wrappers zero-pad "
            f"to the strip automatically; when calling the builders directly "
            f"pad Lx or pick a LaunchConfig.pde_strip dividing it")
    if scheme == "order2" and T < 2:
        raise ValueError(
            f"Goursat strip height T={T} cannot run the order-2 stencil, "
            f"whose skew reads span two refined rows — set "
            f"LaunchConfig.pde_strip >= 2 (or scheme='order1')")
    return R


def build_fwd(batch: int, Lx: int, Ly: int, *, T: int, lam1: int, lam2: int,
              save_cps: bool, interpret: bool, scheme: str = "order1",
              interior_dtype: str = "float32"):
    """Forward solver: returns ``f(delta (batch, Lx, Ly)) -> k (batch,)``,
    or ``(k, cps)`` with ``save_cps``.

    Lx must be a multiple of R = T >> lam1 (ops.py zero-pads: Δ = 0 rows/cols
    leave the Goursat solution invariant since A(0) = B(0) = 1; the order-2
    stencil preserves this because B₂(0) = 1 and C(0) = 0).
    """
    R = check_strip(T, lam1, Lx, scheme)
    n_strips = Lx // R
    ny = Ly << lam2
    W = strip_width(ny, T)
    kern = functools.partial(fwd_kernel, T=T, lam1=lam1, lam2=lam2, ny=ny,
                             save_cps=save_cps, scheme=scheme,
                             interior_dtype=interior_dtype)
    out_shape = [jax.ShapeDtypeStruct((1, batch), jnp.float32)]
    out_specs = [pl.BlockSpec((1, batch), lambda b, s: (0, 0))]
    if save_cps:
        CR = cps_lanes(T)
        out_shape.append(jax.ShapeDtypeStruct((batch, n_strips, CR, W),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, CR, W),
                                      lambda b, s: (b, s, 0, 0)))
    call = pl.pallas_call(
        kern,
        grid=(batch, n_strips),
        in_specs=[pl.BlockSpec((1, R, Ly), lambda b, s: (b, s, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[vmem_scratch((W, T)), vmem_scratch((W, T))],
        compiler_params=compiler_params(W, T, 2),
        interpret=interpret,
        name=KERNEL_NAMES["fwd_ckpt" if save_cps else "fwd"],
    )

    def run(delta):
        k, *cps = call(delta)
        return (k[0], *cps) if save_cps else k[0]

    return run


def check_pack(batch: int, pack: int) -> int:
    """Packs of ``pack`` pairs in ``batch``; ops.py pads the batch."""
    if LANES % pack:
        raise ValueError(f"{pack} pairs per program do not divide {LANES}")
    if batch % pack:
        raise ValueError(
            f"batch={batch} is not a multiple of the {pack} pairs per "
            f"program — the ops.py wrappers pad it with zero increments")
    return batch // pack


def build_fwd_fused(batch: int, Lx: int, Ly: int, d: int, *, T: int,
                    lam1: int, lam2: int, interpret: bool,
                    scheme: str = "order1", interior_dtype: str = "float32",
                    pack: int | None = None):
    """Fused-Δ forward: ``f(dx (B, Lx, d), dy (B, Ly, d)) -> k (B,)``.

    Grid (B/P, n_strips): each program solves a pack of P pairs, one per
    sublane of every (P, T) wavefront row, and writes their values to P
    lanes of a lane-dense output row.  P is ``fused_pack``'s unless
    ``pack`` says otherwise; B must be a multiple of P (ops.py pads it).
    """
    R = check_strip(T, lam1, Lx, scheme)
    n_strips = Lx // R
    ny = Ly << lam2
    W = strip_width(ny, T)
    pack = pack or fused_pack(ny, T, batch)
    n_packs = check_pack(batch, pack)
    kern = functools.partial(fused_fwd_kernel, T=T, lam1=lam1, lam2=lam2,
                             ny=ny, scheme=scheme,
                             interior_dtype=interior_dtype)
    call = pl.pallas_call(
        kern,
        grid=(n_packs, n_strips),
        in_specs=[pl.BlockSpec((pack, R, d), lambda b, s: (b, s, 0)),
                  pl.BlockSpec((pack, Ly, d), lambda b, s: (b, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, LANES), lambda b, s: (0, pack_block(b, pack)[0])),
        out_shape=jax.ShapeDtypeStruct((1, pack_lanes(batch)), jnp.float32),
        scratch_shapes=[vmem_scratch((W * pack, T)),
                        vmem_scratch((W * pack, T))],
        compiler_params=compiler_params(W, T, 2, pack),
        interpret=interpret,
        name=KERNEL_NAMES["fwd_fused"],
    )
    return lambda dx, dy: call(dx, dy)[0, :batch]


def build_gram_fused(Bx: int, By: int, Lx: int, Ly: int, d: int, *, T: int,
                     lam1: int, lam2: int, interpret: bool,
                     scheme: str = "order1", interior_dtype: str = "float32",
                     pack: int | None = None):
    """Fused-Δ Gram ``f(dX (Bx, Lx, d), dY (By, Ly, d)) -> (Bx, By)``: grid
    over (row path, pack of P col paths, strip), P as in
    ``build_fwd_fused``; dx/dy blocks are fetched from the ORIGINAL
    increment arrays by index map — neither Δ nor any pairwise replication
    of the paths ever exists in HBM.  By must be a multiple of P (ops.py
    pads it)."""
    R = check_strip(T, lam1, Lx, scheme)
    n_strips = Lx // R
    ny = Ly << lam2
    W = strip_width(ny, T)
    pack = pack or fused_pack(ny, T, By)
    n_packs = check_pack(By, pack)
    kern = functools.partial(fused_gram_kernel, T=T, lam1=lam1, lam2=lam2,
                             ny=ny, scheme=scheme,
                             interior_dtype=interior_dtype)
    call = pl.pallas_call(
        kern,
        grid=(Bx, n_packs, n_strips),
        in_specs=[pl.BlockSpec((1, R, d), lambda a, b, s: (a, s, 0)),
                  pl.BlockSpec((pack, Ly, d), lambda a, b, s: (b, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, 1, LANES), lambda a, b, s: (a, 0, pack_block(b, pack)[0])),
        out_shape=jax.ShapeDtypeStruct((Bx, 1, pack_lanes(By)), jnp.float32),
        scratch_shapes=[vmem_scratch((W * pack, T)),
                        vmem_scratch((W * pack, T))],
        compiler_params=compiler_params(W, T, 2, pack),
        interpret=interpret,
        name=KERNEL_NAMES["gram_fused"],
    )
    return lambda dX, dY: call(dX, dY)[:, 0, :By]


def vmem_scratch(shape, dtype=jnp.float32):
    """VMEM scratch allocator (TPU target; also honoured by interpret mode)."""
    return pltpu.VMEM(shape, dtype)
