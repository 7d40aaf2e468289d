"""Pallas TPU kernel for the groupby one-hot contraction.

The MXU groupby path (:mod:`bqueryd_tpu.ops.groupby`) reduces stacked bf16
rows (count flags, value limbs, float hi/lo pairs) against the one-hot of the
group codes.  XLA already fuses the one-hot formation into the dot operand;
this Pallas kernel makes that explicit and keeps the whole contraction in
VMEM: per grid step it DMAs one ``[R, K]`` row block plus one ``[K]`` code
block, forms ``[KT, G]`` one-hot tiles on the fly (broadcasted-iota compare —
never materialized to HBM), feeds the MXU, and accumulates the block's
``[R, G]`` partial in a float32 VMEM scratch.  Per-block partials stay below
2^24 (the caller bounds K * max-row-value), so the float32 accumulation is
exact and the caller's uint64 block reduction preserves bit-exact int64
sums — identical numerics to the XLA path by construction.

The kernel is traced with x64 disabled (Mosaic rejects the i64 loop/index
constants that x64 mode inserts) — safe because every operand is explicitly
i32/bf16/f32.

Usage is opt-in via ``BQUERYD_TPU_PALLAS=1`` (auto-interpret on CPU, where the
same kernel runs under the Pallas interpreter for test coverage).  The
default stays the XLA dot; the fused formation saves the one-hot
regeneration VPU pass per dot and matters at cardinalities where the
``[nb, K, G]`` operand would otherwise spill.  ``chip_smoke.py`` compiles
both kernels with Mosaic at 10 M rows on every run, so an opt-in kernel the
TPU compiler refuses cannot stay in the tree unnoticed.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per grid block; must match ops.groupby._MATMUL_BLOCK so the caller's
#: exactness bound (block sums < 2^24) applies unchanged
BLOCK_K = 32768

#: sublane multiple for the stacked-rows operand.  16, not 8: the lhs block
#: is bf16, whose native Mosaic tile is (16, 128) — an 8-sublane bf16 block
#: relies on small-tile support that an older Mosaic may lack, and one row
#: of zero padding costs nothing
_SUBLANE = 16


def pallas_enabled():
    """Opt-in flag: BQUERYD_TPU_PALLAS=1 routes the groupby contraction
    through the Pallas kernel (interpreted on CPU backends)."""
    return os.environ.get("BQUERYD_TPU_PALLAS", "0") == "1"


def _round_up(x, mult):
    return -(-x // mult) * mult


def _make_kernel(n_rows, n_groups, tile_k):
    def kernel(codes_ref, lhs_ref, out_ref, acc_ref):
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def body(kt, carry):
            off = kt * jnp.int32(tile_k)
            c = codes_ref[pl.ds(off, tile_k)]  # [KT] i32
            iota = lax.broadcasted_iota(jnp.int32, (tile_k, n_groups), 1)
            one_hot = (c[:, None] == iota).astype(jnp.bfloat16)  # [KT, G]
            lhs = lhs_ref[:, pl.ds(off, tile_k)]  # [R, KT] bf16
            acc_ref[:] += lax.dot_general(
                lhs,
                one_hot,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return carry

        lax.fori_loop(
            jnp.int32(0), jnp.int32(BLOCK_K // tile_k), body, jnp.int32(0)
        )
        out_ref[0] = acc_ref[:]

    return kernel


#: the 1-D int32 codes block is loaded at a dynamic offset ``kt * tile``, and
#: Mosaic only accepts such a load where it can prove the offset lands on a
#: native 1-D int32 tile boundary (8 sublanes x 128 lanes): every K tile of
#: both kernels is a multiple of this.  Smaller tiles were refused on the
#: v5e ("cannot statically prove that index in dimension 0 is a multiple of
#: 1024"; they had only ever run under the interpreter).
_CODES_TILE = 1024

#: smallest inner K tile; also sets the group-count ceiling of the Pallas
#: route (see :func:`pallas_groups_limit`)
_MIN_TILE = _CODES_TILE

#: bf16 one-hot tile budget in elements (~4 MB of the ~16 MB VMEM)
_ONEHOT_BUDGET = 1 << 21


def pallas_groups_limit():
    """Max group count the kernel can run without its smallest one-hot tile
    overflowing the VMEM budget: above this the caller must stay on the XLA
    path (which it does anyway past ``matmul_groups_limit`` unless the env
    knob raised it — the round-3 VMEM hole was exactly that combination)."""
    return _ONEHOT_BUDGET // _MIN_TILE


#: total VMEM the kernel may plan for (v5e has ~16 MB; leave headroom for
#: Mosaic's own buffers)
_VMEM_BUDGET_BYTES = 12 << 20


def fits_vmem(n_rows, n_groups):
    """Whether the kernel's working set fits the VMEM budget for this shape.

    The group ceiling alone is not enough: the f32 accumulator scratch and
    output block scale with ``n_rows * n_groups`` (many stacked limb rows at
    high cardinality can exhaust VMEM even under the one-hot ceiling), and
    the double-buffered lhs block with ``n_rows * BLOCK_K``."""
    if n_groups > pallas_groups_limit():
        return False
    rpad = _round_up(max(n_rows, 1), _SUBLANE)
    gpad = _round_up(max(n_groups, 1), 128)
    tile = _tile_k(gpad)
    need = (
        tile * gpad * 2            # bf16 one-hot tile
        + 2 * rpad * gpad * 4      # f32 accumulator scratch + output block
        + 2 * rpad * BLOCK_K * 2   # double-buffered bf16 lhs block
        + 2 * BLOCK_K * 4          # double-buffered i32 codes block
    )
    return need <= _VMEM_BUDGET_BYTES


def _tile_k(n_groups):
    """Largest inner K tile whose bf16 one-hot stays within ~4 MB of VMEM,
    shrinking to ``_MIN_TILE`` at high group counts.

    Restricted to powers of two so the tile always divides ``BLOCK_K`` —
    a non-divisor would truncate the block loop and silently drop rows."""
    budget = _ONEHOT_BUDGET // max(n_groups, 128)
    tile = _MIN_TILE
    while tile * 2 <= min(budget, 2048):
        tile *= 2
    return tile


def _call(codes_flat, lhs, n_rows, n_groups, interpret):
    nb = codes_flat.shape[0] // BLOCK_K
    tile = _tile_k(n_groups)
    return pl.pallas_call(
        _make_kernel(n_rows, n_groups, tile),
        out_shape=jax.ShapeDtypeStruct((nb, n_rows, n_groups), jnp.float32),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLOCK_K,), lambda b: (b,), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (n_rows, BLOCK_K), lambda b: (0, b), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, n_rows, n_groups),
            lambda b: (b, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.VMEM((n_rows, n_groups), jnp.float32)],
        interpret=interpret,
    )(codes_flat, lhs)


#: group-tile width of the high-cardinality kernel: one lane-multiple of
#: output groups computed per outer grid step.  Env-tunable for hardware
#: sweeps (a fresh process per setting: the values freeze into each traced
#: program signature).
def _hicard_gt():
    return int(os.environ.get("BQUERYD_TPU_PALLAS_HICARD_GT", 2048))


#: inner K tile of the high-cardinality kernel ([KT, GT] bf16 one-hot =
#: 4 MB VMEM at the defaults); a multiple of ``_CODES_TILE``
def _hicard_kt():
    return int(os.environ.get("BQUERYD_TPU_PALLAS_HICARD_KT", _CODES_TILE))

#: uint32 accumulator bound: every 8-bit limb row's TOTAL sum must stay
#: below 2^32 (limb values <= 255), so rows beyond this need the caller to
#: split the call or take another path
HICARD_MAX_ROWS = (1 << 32) // 256


def hicard_groups_limit():
    """Group-count ceiling of the high-cardinality kernel.  The one-hot
    contraction costs ``rows * groups`` MXU MACs; past a few hundred
    thousand groups the sort path wins back.  Tunable for hardware A/B
    (BQUERYD_TPU_PALLAS_HICARD_GROUPS)."""
    return int(
        os.environ.get("BQUERYD_TPU_PALLAS_HICARD_GROUPS", 1 << 18)
    )


def hicard_fits_vmem(n_rows):
    """Whether ``n_rows`` stacked reduction rows fit the high-cardinality
    kernel's VMEM plan under the current (env-tunable) tile sizes — the
    double-buffered lhs blocks dominate as the row count grows."""
    rpad = _round_up(max(n_rows, 1), _SUBLANE)
    kt, gt = _hicard_kt(), _hicard_gt()
    need = (
        kt * gt * 2                      # bf16 one-hot tile
        + rpad * gt * 4 * 2              # i32 out block (+revisit headroom)
        + 2 * rpad * BLOCK_K * 2         # double-buffered bf16 lhs block
        + 2 * BLOCK_K * 4                # double-buffered i32 codes block
    )
    return need <= _VMEM_BUDGET_BYTES


def _make_hicard_kernel(tile_k, gt):
    def kernel(codes_ref, lhs_ref, out_ref):
        # out block revisited across the inner (row-block) grid dim:
        # zero once, accumulate each block's exact f32 partial in int32
        @pl.when(pl.program_id(1) == 0)
        def _zero():
            out_ref[...] = jnp.zeros_like(out_ref)

        g0 = pl.program_id(0) * jnp.int32(gt)

        def body(kt, carry):
            off = kt * jnp.int32(tile_k)
            c = codes_ref[pl.ds(off, tile_k)]  # [KT] i32
            iota = g0 + lax.broadcasted_iota(jnp.int32, (tile_k, gt), 1)
            one_hot = (c[:, None] == iota).astype(jnp.bfloat16)
            lhs = lhs_ref[:, pl.ds(off, tile_k)]  # [R, KT] bf16
            part = lax.dot_general(
                lhs,
                one_hot,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # the K-tile partial is < 2^24 (tile_k * limb max 255), exact
            # in f32 and in the i32 convert; i32 accumulation wraps mod
            # 2^32, which the caller's uint32 bitcast recombination
            # absorbs (limb totals bounded by HICARD_MAX_ROWS * 255)
            out_ref[...] += part.astype(jnp.int32)
            return carry

        lax.fori_loop(
            jnp.int32(0), jnp.int32(BLOCK_K // tile_k), body, jnp.int32(0)
        )

    return kernel


@functools.partial(
    jax.jit, static_argnames=("n_rows", "n_groups", "interpret")
)
def onehot_rows_dot_hicard(codes, rows, n_rows, n_groups, interpret=False):
    """High-cardinality variant: ``out[r, g] = sum_k rows[r, k] *
    (codes[k] == g)`` with the block reduction performed IN-KERNEL in
    int32 (mod 2^32), so the output is ``[R, G]`` instead of the base
    kernel's per-block ``[nb, R, G]`` — at 70k+ groups the per-block
    partials would otherwise materialize gigabytes in HBM.

    INT rows only (count flags and 8-bit limbs, values <= 255): the mod-2^32
    accumulation is exact for them below ``HICARD_MAX_ROWS`` rows; float
    Dekker limbs have no wrap-free encoding here and must stay off this path.

    codes: int32[n] folded group codes (negative = contributes nowhere)
    rows:  bf16[R, n] stacked int reduction rows
    Returns uint32[R16, G128] limb totals mod 2^32 (R16/G128 rounded up to
    tile multiples — callers slice ``[:R, :G]`` and zero-extend to uint64).
    """
    n = codes.shape[0]
    if n > HICARD_MAX_ROWS:
        raise ValueError(
            f"n={n} exceeds HICARD_MAX_ROWS={HICARD_MAX_ROWS}: a limb "
            "total could wrap twice; split the call or use the sort path"
        )
    if not hicard_fits_vmem(n_rows):
        # the invariant lives here, not only in the dispatcher's boolean
        # (same rule as onehot_rows_dot): past this row count the lhs
        # double-buffer overflows VMEM and Mosaic's failure mode is an
        # opaque exhaustion
        raise ValueError(
            f"n_rows={n_rows} exceeds the hicard kernel's VMEM budget; "
            "use the scatter path"
        )
    npad = _round_up(max(n, 1), BLOCK_K)
    rpad = _round_up(n_rows, _SUBLANE)
    gt, kt = _hicard_gt(), _hicard_kt()
    if (
        kt < _CODES_TILE
        or gt < 128
        or kt % _CODES_TILE != 0
        or BLOCK_K % kt != 0
        or gt % 128 != 0
    ):
        # sweep-knob hygiene: a non-divisor KT silently drops rows in the
        # inner loop, one off the codes tile grid is refused by Mosaic; a
        # non-lane-multiple GT breaks the output tiling.  Positivity
        # first: the modulo checks themselves divide by kt
        raise ValueError(
            f"invalid hicard tiles KT={kt} (must divide {BLOCK_K} and be "
            f"a multiple of {_CODES_TILE}) / GT={gt} (must be a positive "
            "multiple of 128)"
        )
    gpad = _round_up(n_groups, gt)
    codes_p = jnp.pad(
        codes.astype(jnp.int32), (0, npad - n), constant_values=-1
    )
    rows_p = jnp.pad(
        rows.astype(jnp.bfloat16), ((0, rpad - n_rows), (0, npad - n))
    )
    nb = npad // BLOCK_K
    ngt = gpad // gt
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _make_hicard_kernel(kt, gt),
            out_shape=jax.ShapeDtypeStruct((rpad, gpad), jnp.int32),
            # row-block dim innermost: the output block stays resident in
            # VMEM while the whole row range accumulates into it
            grid=(ngt, nb),
            in_specs=[
                pl.BlockSpec(
                    (BLOCK_K,), lambda g, b: (b,), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec(
                    (rpad, BLOCK_K),
                    lambda g, b: (0, b),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (rpad, gt),
                lambda g, b: (0, g),
                memory_space=pltpu.VMEM,
            ),
            interpret=interpret,
        )(codes_p, rows_p)
    return lax.bitcast_convert_type(out, jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("n_rows", "n_groups", "interpret")
)
def onehot_rows_dot(codes, rows, n_rows, n_groups, interpret=False):
    """``out[b, r, g] = sum_k rows[r, b*K+k] * (codes[b*K+k] == g)``.

    codes: int32[n] folded group codes (negative = contributes nowhere)
    rows:  bf16[R, n] stacked reduction rows (R == n_rows)
    Returns float32[nb, R16, G128] where R16/G128 are R and n_groups rounded
    up to hardware tile multiples — callers slice ``[:, :R, :G]``.
    """
    if not fits_vmem(n_rows, n_groups):
        # the invariant lives here, not only in the dispatcher's boolean:
        # past this shape the working set overflows the VMEM budget, and
        # Mosaic's failure mode is an opaque exhaustion
        raise ValueError(
            f"n_rows={n_rows} x n_groups={n_groups} exceeds the Pallas "
            "kernel's VMEM budget; use the XLA path"
        )
    n = codes.shape[0]
    npad = _round_up(max(n, 1), BLOCK_K)
    rpad = _round_up(n_rows, _SUBLANE)
    gpad = _round_up(n_groups, 128)
    codes_p = jnp.pad(
        codes.astype(jnp.int32), (0, npad - n), constant_values=-1
    )
    rows_p = jnp.pad(
        rows.astype(jnp.bfloat16), ((0, rpad - n_rows), (0, npad - n))
    )
    with jax.enable_x64(False):
        return _call(codes_p, rows_p, rpad, gpad, interpret)


# compile/call accounting (obs.profile): the Pallas entry points land in the
# same jit-cache hit/miss counters and compile-seconds histogram as the XLA
# paths — the purity lint's jit-uninstrumented rule cross-checks this.  The
# wrapper passes straight through when called under an outer trace (the
# use_pallas route inside _partial_tables_mm), so instrumenting here never
# double-counts.
from bqueryd_tpu.obs import profile as _obsprofile  # noqa: E402

onehot_rows_dot = _obsprofile.instrument(
    "ops.pallas_onehot", onehot_rows_dot
)
onehot_rows_dot_hicard = _obsprofile.instrument(
    "ops.pallas_onehot_hicard", onehot_rows_dot_hicard
)
