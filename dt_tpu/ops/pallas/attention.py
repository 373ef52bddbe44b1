"""Flash attention: fused online-softmax attention as a Pallas TPU kernel.

The reference's long-context ceiling is the cuDNN fused RNN
(``src/operator/cudnn_rnn-inl.h:1`` — SURVEY §5.7: no attention anywhere in
the 2018 tree); this framework makes long-context first-class, so the
single-device attention hot path gets the same treatment the reference
gave its RNN cells: a hand-fused kernel.  Forward is a Pallas kernel:
grid (batch*heads, q_tiles, k_tiles), online-softmax accumulation in VMEM
scratch across the sequential key axis, O(tile²) VMEM instead of O(S²)
HBM for the score matrix.

The tiles are chosen from the shape (``forward_tiles``): for each side the
largest of ``FORWARD_TILES`` that divides the (padded) length, as long as
one grid step's blocks, scratch and float32 scores fit ``VMEM_BUDGET``.  A
grid step costs about 0.4 us whatever it computes, so at 128 x 128 the
8,192 and 65,536 steps a call of the benchmark's two cells were the
kernel's whole time (PERF.md section 6, PR 29).  q, k and v go to the MXU
in the type they are stored in, with float32 accumulation; the
probabilities are cast to ``v``'s type for the second product; max, exp,
the running sum and the accumulator are float32.  Under ``causal`` a tile
above the diagonal computes nothing and fetches nothing (its index map
repeats the last block needed), a tile below it runs unmasked, and a tile
the diagonal crosses is **walked in sub-blocks** of ``SUB_BLOCK`` (128)
positions a side: for each row block of queries the product against the
key sub-blocks at or before it alone, the compare on the one sub-block
the diagonal passes through, the online softmax of those rows (36 of a
1,024-tile's 64 sub-blocks, 10 of a 512-tile's 16; see "Inside a crossed
tile" below).  ``flash_attention``'s ``block_q`` / ``block_k`` override
the choice of tiles.

Grouped-query attention needs no spread heads: ``k`` and ``v`` come with
their own head count ``KV``, a divisor of the queries' ``H``, as (B * KV,
SK, D) beside q's (B * H, S, D), and the key and value block of a grid step
is fetched from row ``b // rep`` (``rep = H // KV``: ``_kv_row`` in both
kernels' index maps, under every mask rule), so a group's ``rep`` query
heads read one head's tiles from an array a ``rep``-th the size and no
caller runs ``jnp.repeat`` (PERF.md section 6, PR 44: the spread was about
1.1 ms a layer and pass of XLA over 268 MB arrays in the mixed-attention
cell, and 0.6 GB of its step's temporaries).  The backward's dk
and dv leave the kernel one a query head and are summed over each group in
float32 outside it (``_sum_groups``): summed inside, a group's dq
accumulators, each a whole sequence of float32, would all have to stay in
VMEM.  With ``KV == H`` the index maps and the programs are what they were.

A caller that holds its heads as the projections leave them hands
``flash_attention`` rank-3 operands, q ``(B, S, H * D)`` and k, v ``(B, SK,
KV * D)`` (``lane_tiled``: ``D % 128 == 0``, the lengths multiples of 8), and
then neither kernel's operand is turned at all (PERF.md section 6, PR 45): a
block is ``(1, block, D)`` at ``(b // H, tile, b % H)``, one head's ``D``
columns of ``block`` rows that lie ``H * D`` apart (``_head_at``).  The
grid, the bodies, the tiles and the log-sum-exp's ``(B * H, ...)`` rows are
the same; out, dq, dk, dv come back in that layout.  What XLA computes
beside the kernels there (delta, the groups' sums) goes over the view
``by_head`` gives, ``(B, S / 8, H, 8, D)``: under the TPU's (8, 128) tiling
that view is the ``(B, S, H * D)`` array's own bytes, where a ``(B, S, H,
D)`` view is tiled over ``(H, D)`` and costs a pass over the array for every
``reshape`` (the compiler's count for one step of the mixed-attention cell
rose by 33 GB with the kernels in this layout and the layer's work left on
``(B, S, H, D)`` arrays, and fell by 70 GB with it on the view:
``models/routed_lm.py`` ``RotaryAttention``).  So the rank decides, and a
caller with ``(B, S, H, D)`` operands, at any head size, gets heads after
the batch, ``(B * H, S, D)``, as it always did, to the jaxpr.

Backward is the standard flash backward from the saved log-sum-exp, one
Pallas call named ``flash_bwd`` (its events carry that name in a trace,
where the forward's carry its caller's): grid (batch*heads, k_tiles,
q_tiles), the tile held keys by queries (``s^T = k q^T``) so that the
log-sum-exp and ``delta = rowsum(do * out)`` (float32, from XLA) are lane
rows and dv, dk are plain products; dk, dv accumulate in float32 VMEM
scratch over the query axis, dq over both axes in a scratch for the whole
sequence.  Five products and one exponential a tile pair.  Operands as
stored and float32 accumulation, as the forward; ``p`` and ``ds`` are cast
to the operands' type for the products they enter.  Pruned under ``causal``
as the forward is, and a crossed tile walked as the forward's with the
sides exchanged (for each row block of keys the query sub-blocks at or
after it).  Its tiles are its own (``backward_tiles``: at most 512
a side), whatever the forward's are; nothing chooses another backward.  The
whole sequence's dq in VMEM bounds the length one device takes: in
bfloat16 the tiles fall to 128 x 128 from about 23,000 positions and the
compiler (given twice ``VMEM_BUDGET``) refuses from about 48,000
(``ring_attention`` is the path beyond one device).  ``DEFAULT_BLOCK``
(128) is what callers pad sequences to.

Beside ``causal`` both kernels take a mask rule, ``BlockDiffusionMask``
(training by diffusion over blocks: ``[noisy ; clean]`` halves, a block
diagonal, two block-causal triangles and an empty quadrant, about a quarter
of the square): a tile's fate (skip, run unmasked, crossed by an edge) and
what a skipped step fetches follow from its offsets, as under ``causal``;
the crossed tiles are the three quadrants' diagonal tiles, each walked by
its own edge (the block diagonal: a strip one sub-block wide, 8 of a
1,024-tile's 64 sub-blocks; the strict and the inclusive block-causal
triangle: 36 of 64), the compare on the diagonal sub-blocks alone; the
kernels are then named ``flash_fwd_bd`` and ``flash_bwd_bd``.  The rule is
static: without it the programs are what they were.

A third static rule is a band: ``WindowMask(window)`` (sliding-window
attention: query ``t`` sees the keys ``t - window < s <= t``; it implies
``causal``).  A tile's fate and what a skipped step fetches follow from
offsets here too, but nearly all of the other axis would be skipped (at
8,192 positions, a window of 512 and tiles of 512 a query tile touches two
key tiles of sixteen), and a grid step costs its 0.4 us whether it computes
or not.  So **the forward's key axis and the backward's query axis span
only the tiles a band can touch** (``WindowMask.key_steps``,
``query_steps``: with square tiles of ``b``, ``ceil((window - 1) / b) + 1``)
and the index maps add the tile's own offset: the forward's step ``j`` of
query tile ``qi`` is key tile ``hi(qi) - (steps - 1) + j``, ``hi`` the
diagonal tile's; the backward's step ``j`` of key tile ``ki`` is query tile
``lo(ki) + j``.  The steps that fall outside the sequence (the forward's
first at its start, the backward's last at its end) compute nothing and
name the band's first (last) tile, which the neighbouring step fetches
anyway.  dq still accumulates in a scratch for the whole sequence.  The
tiles are square and no wider than the window (``WindowMask.tiles``:
512 x 512 at a window of 512, whose tiles hold twice the band's pairs in
the fewest steps; PERF.md section 6, PR 41, has the sweep).  Where the
window is whole tiles every tile that an edge crosses is walked: the
diagonal tile by the causal edge alone, the tile a window before it by
the band's edge alone (10 of 16 sub-blocks each at 512, so 1.25 squares
are computed for the band's one); tiles between them run unmasked, and a
window that is not whole tiles is masked tile by tile under both edges
(``_in_band``) as before.  The kernels are
named ``flash_win_fwd`` and ``flash_win_bwd`` (no ``flash_fwd`` or
``flash_bwd`` in them: a decoder that alternates windowed and full layers
counts each kind's events alone), and their results carry the names
``flash_win_out`` and ``flash_win_lse`` for a remat policy.

A second rule is *data*: ``SelectedKeysMask`` (attention over the keys a
learned indexer picked for each query, ``ops/sparse_index.py``).  Which keys
a query may see comes from an array, the ``Selection``, made once a layer
outside the kernels and read a tile at a time by both: a bitmap packed
``SEL_GROUP`` = 32 x 128 keys to a row of 128 int32 words, bit ``b`` of lane
``l`` of group ``g`` standing for key ``g * 4096 + b * 128 + l``, so that a
tile's 128-key column blocks are ``(words >> b) & 1``: a shift of the block
as it lies, no gather and no turn.  The forward reads ``by_query``
``(B, T / 4096, T, 128)`` (rows are queries, bits keys), one ``(block_q,
128)`` block a grid step, fetched once for the key tiles of one group; the
backward, whose tile lies keys by queries, reads ``by_key`` (rows are keys,
bits queries).  A tile's fate is data too: ``blocks`` says which 128 x 128
blocks hold a selected pair, each kernel is handed the any-reduction over
its own tiles as a scalar-prefetch table, and a tile whose entry is 0
computes nothing.  The rule composes with ``causal``: tiles above the
diagonal neither run nor fetch, whatever the table says, and a tile the
diagonal crosses is held to both (walked as ``causal``'s, the bitmap on
every sub-block computed).  33.5 MB a bitmap at 16,384 positions (an
int8 square would be 268 MB).  The kernels are then named ``flash_fwd_sel``
and ``flash_bwd_sel``; they compute every pair of a tile (below the
diagonal: of a sub-block) that runs, so the result is exact and the work is
the tiles', not the selection's.

Inside a crossed tile (PERF.md section 6, PR 42).  With square tiles that
divide the length (and the half, and the window) the place of an edge
inside a crossed tile is static: ``crossed_kinds`` names the grid's kinds
of crossed tile, each an ``_Edge`` (a band ``lo <= (k + gap) // unit - q //
unit <= hi`` over positions counted from the tile's corner), and the
kernels walk such a tile in a Python loop over static slices of the refs,
unrolled at trace time.  A sub-block no pair of which is allowed is not
computed (it added exact zeros before), one all of whose pairs are runs
unmasked, and only one the edge passes through is compared and masked.  In
the forward all the row blocks' score products come before the first
softmax, in the backward each kind of product and of arithmetic runs over
all the row blocks before the next: row block after row block the walks
took a quarter longer than that.  Where the geometry gives no static
pattern (rectangular tiles a caller forces, a window or a block that is not
a whole part of a tile) every tile that runs is computed whole, as before;
a call without ``causal`` or a rule has no crossed tile and its program is
what it was.  The ``# flash_tiles`` / ``# flash_bwd_tiles`` debug lines and
the gauges ``flash.pairs_computed_pct`` / ``flash.bwd_pairs_computed_pct``
say which a shape took (``computed_tiles``).

Composes with the distributed layer: ``ring_attention`` shards the
sequence over the mesh and runs blockwise attention per shard; this
kernel is the single-device fusion.  ``TransformerLM(seq_parallel="flash")``,
``GroupedQueryAttention(attention="flash")`` and ``RotaryAttention(attention=
"flash")`` (``models/routed_lm.py``, under any of the mask rules or plain
``causal``) select it.

Parity: ``dt_tpu.parallel.ring_attention.full_attention`` is the oracle;
tests cover fwd/bwd, causal and full, interpret (CPU) mode.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.ops.pallas.kernels import _default_interpret

logger = logging.getLogger("dt_tpu")

NEG_INF = -1e30
# what callers pad to (TransformerLM, GroupedQueryAttention); the tiles of
# either pass are derived from the shape (forward_tiles, backward_tiles)
DEFAULT_BLOCK = 128
_LANES = 128
# the forward's candidate tiles, largest first (PERF.md section 6, PR 29:
# the sweep over {256, 512, 1024} on either side at the cells' shapes)
FORWARD_TILES = (1024, 512, 256, 128)
# the backward's: at 1,024 positions three 512 x 512 tiles of four beat the
# whole square, and 1,024 x 1,024 is over VMEM_BUDGET by backward_vmem_bytes
# (PERF.md section 6, PR 31: the sweep)
BACKWARD_TILES = (512, 256, 128)
# what one grid step may hold in VMEM by tile_vmem_bytes' reckoning; the
# compiler is given twice that (v5e has 128 MiB of it, 16 MiB scoped by
# default)
VMEM_BUDGET = 24 << 20
# a tile that an edge of the mask crosses is walked in sub-blocks of this
# many positions a side (``_Edge``; PERF.md section 6, PR 42: 128 beat 256
# in both passes at every cell's shape, at head sizes 64 and 128 alike)
SUB_BLOCK = 128


def tile_vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM one grid step of the forward holds, reckoned from the shapes:
    the double-buffered q, k, v and output blocks and log-sum-exp tile,
    the float32 scratch (rows padded to a lane tile), and the tile's
    float32 scores and probabilities with the probabilities' copy in the
    operands' type."""
    dl = -(-d // _LANES) * _LANES
    blocks = 2 * (2 * block_q + 2 * block_k) * dl * itemsize \
        + 2 * max(block_q // _LANES, 8) * _LANES * 4
    scratch = block_q * (dl + 2 * _LANES) * 4
    scores = block_q * block_k * (3 * 4 + itemsize)
    return blocks + scratch + scores


def _largest_tiles(candidates, s: int, sk: int, fits):
    """Of the pairs of ``candidates`` that divide the lengths and that
    ``fits`` admits, the largest (by scores a tile, then by query rows); a
    length below a tile is one tile of a smaller one.  A length that no
    candidate divides raises."""
    def dividing(n):
        got = [t for t in candidates if n % t == 0]
        if not got:
            raise ValueError(f"seq length {n} must be a multiple of "
                             f"{candidates[-1]}")
        return got
    pairs = [(bq, bk) for bq in dividing(s) for bk in dividing(sk)]
    fit = [p for p in pairs if fits(*p)]
    return max(fit or pairs[-1:], key=lambda p: (p[0] * p[1], p[0]))


def forward_tiles(s: int, sk: int, d: int, itemsize: int, mask=None):
    """The forward's (block_q, block_k) for query length ``s``, key length
    ``sk``, head size ``d`` and operands of ``itemsize`` bytes: the largest
    pair of ``FORWARD_TILES`` (``_largest_tiles``) that keeps
    ``tile_vmem_bytes`` within ``VMEM_BUDGET``.  Under a
    ``BlockDiffusionMask`` a tile lies in one half, so it divides
    ``mask.half``: at 4,096 positions a half 1,024 x 1,024, which runs 1.5
    times the rule's area where 512 x 512 runs 1.25 times and takes 1.6
    times as long (9.36 against 14.95 ms a call, PERF.md section 6, PR
    34)."""
    if isinstance(mask, WindowMask):
        return mask.tiles(s)
    if mask is not None:
        s = sk = mask.half
    return _largest_tiles(
        FORWARD_TILES, s, sk,
        lambda bq, bk: tile_vmem_bytes(bq, bk, d, itemsize) <= VMEM_BUDGET)


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The mask of training by diffusion over blocks (BD3-LM, SDAR), a rule
    the kernels take beside ``causal``.  The sequence is two halves of
    ``half`` positions each, ``[noisy ; clean]``, both cut into blocks of
    ``block`` positions; with ``qb``, ``kb`` the blocks of a query and a key
    inside their halves,

        noisy query, noisy key:  qb == kb      (a block diagonal)
        noisy query, clean key:  kb <  qb      (a block-causal triangle)
        clean query, clean key:  kb <= qb      (the other)
        clean query, noisy key:  never         (the empty quadrant)

    so about ``half^2`` of the ``4 half^2`` square is needed.  Static: a
    tile's fate (skip, run unmasked, run masked) follows from its offsets,
    and a skipped step's index map names a tile its neighbour fetches
    anyway.  ``half`` is a multiple of ``block`` and of the tiles (a caller
    pads each half: a padded key lies in a block after every real query's,
    so the rule itself hides it).  The methods take a tile's grid indices
    as Python or traced integers."""
    half: int
    block: int

    _FAR = 1 << 30

    def __post_init__(self):
        if self.block < 1 or self.half % self.block:
            raise ValueError(f"a half of {self.half} positions is not whole "
                             f"blocks of {self.block}")

    def allowed(self, q_pos, k_pos):
        """The rule, element by element, for positions in ``[0, 2 half)``:
        what a dense masked softmax applies (the kernels' oracle)."""
        q_noisy, k_noisy = q_pos < self.half, k_pos < self.half
        qb, kb = (q_pos % self.half) // self.block, \
            (k_pos % self.half) // self.block
        return jnp.where(k_noisy, q_noisy & (qb == kb),
                         jnp.where(q_noisy, kb < qb, kb <= qb))

    def _blocks(self, first, n):
        """First and last block of ``n`` positions from ``first`` (inside a
        half)."""
        return first // self.block, (first + n - 1) // self.block

    def _band(self, q_noisy, k_noisy):
        """``(c1, c2)``: a pair is allowed where ``qb - c2 <= kb <= qb -
        c1``; the empty quadrant's band is out of reach."""
        c1 = jnp.where(k_noisy, jnp.where(q_noisy, 0, self._FAR),
                       jnp.where(q_noisy, 1, 0))
        c2 = jnp.where(k_noisy, 0, self._FAR)
        return c1, c2

    def tile(self, qi, ki, block_q: int, block_k: int):
        """The (qi, ki) tile -> (runs, unmasked, q0, k0, c1, c2): whether
        any pair of it is allowed, whether all are, its first query and key
        position inside their halves, and the band its pairs are held to."""
        nq, nk = self.half // block_q, self.half // block_k
        q_noisy, k_noisy = qi < nq, ki < nk
        q0 = (qi - jnp.where(q_noisy, 0, nq)) * block_q
        k0 = (ki - jnp.where(k_noisy, 0, nk)) * block_k
        (qb_lo, qb_hi), (kb_lo, kb_hi) = self._blocks(q0, block_q), \
            self._blocks(k0, block_k)
        c1, c2 = self._band(q_noisy, k_noisy)
        # kb - qb takes every value between its least and its largest
        runs = (kb_lo - qb_hi <= -c1) & (kb_hi - qb_lo >= -c2)
        unmasked = (kb_hi - qb_lo <= -c1) & (kb_lo - qb_hi >= -c2)
        return runs, unmasked, q0, k0, c1, c2

    def key_tile(self, qi, ki, block_q: int, block_k: int):
        """The key tile the forward's step (qi, ki) fetches: ``ki`` where
        the tile runs, else a tile the query tile needs anyway (the nearest
        before it, or the first), so that a skipped step fetches nothing."""
        nq, nk = self.half // block_q, self.half // block_k
        q_noisy = qi < nq
        q0 = (qi - jnp.where(q_noisy, 0, nq)) * block_q
        qb_lo, qb_hi = self._blocks(q0, block_q)
        # noisy keys: the tiles that hold the query tile's own blocks
        a = qb_lo * self.block // block_k
        b = jnp.minimum(((qb_hi + 1) * self.block - 1) // block_k, nk - 1)
        # clean keys: the tiles up to the last block seen (before it, for a
        # noisy query); -1 where there is none
        last = jnp.where(q_noisy, qb_hi * self.block,
                         (qb_hi + 1) * self.block) - 1
        c = jnp.minimum(last // block_k, nk - 1)
        in_noisy = jnp.where(q_noisy, jnp.clip(ki, a, b), nk)
        in_clean = jnp.where(c >= 0, nk + jnp.clip(ki - nk, 0, c), b)
        return jnp.where(ki < nk, in_noisy, in_clean)

    def query_tile(self, ki, qi, block_q: int, block_k: int):
        """The query tile the backward's step (ki, qi) fetches, as
        ``key_tile``: ``qi`` where the tile runs, else one the key tile
        needs anyway."""
        nq, nk = self.half // block_q, self.half // block_k
        k_noisy = ki < nk
        k0 = (ki - jnp.where(k_noisy, 0, nk)) * block_k
        kb_lo, kb_hi = self._blocks(k0, block_k)
        # a noisy key: the noisy queries of its own blocks, no clean one
        a = kb_lo * self.block // block_q
        b = jnp.minimum(((kb_hi + 1) * self.block - 1) // block_q, nq - 1)
        # a clean key: noisy queries from the first tile with a block after
        # its first (none: nq), clean queries from its first block's tile
        s1 = (kb_lo + 1) * self.block // block_q
        s2 = kb_lo * self.block // block_q
        clean_from = nq + jnp.maximum(qi - nq, s2)
        for_clean = jnp.where((qi < nq) & (s1 < nq), jnp.maximum(qi, s1),
                              clean_from)
        return jnp.where(k_noisy, jnp.clip(qi, a, b), for_clean)

    def tiles_run(self, block_q: int, block_k: int) -> int:
        """How many of the grid's tiles run (of ``4 half^2 / (block_q
        block_k)``): what pruning leaves."""
        nq, nk = 2 * self.half // block_q, 2 * self.half // block_k
        with jax.ensure_compile_time_eval():    # asked while tracing
            return sum(bool(self.tile(qi, ki, block_q, block_k)[0])
                       for qi in range(nq) for ki in range(nk))


def _when_block_diffusion(tile, rule: BlockDiffusionMask, qi, ki,
                          block_q: int, block_k: int):
    """Run ``tile(band)`` for the (qi, ki) tile under ``rule``: not at all
    where no pair of it is allowed, with ``band`` None where all are, else
    with ``band = (q0, k0, c1, c2)`` for ``_band_mask``."""
    runs, unmasked, q0, k0, c1, c2 = rule.tile(qi, ki, block_q, block_k)
    pl.when(runs & jnp.logical_not(unmasked))(
        functools.partial(tile, (q0, k0, c1, c2)))
    pl.when(runs & unmasked)(functools.partial(tile, None))


def _blocks_along(first, axis: int, shape, block: int):
    """The block (of ``block`` positions) of each position from ``first``
    along ``axis`` of ``shape``: a column or a row vector."""
    vec = tuple(n if a == axis else 1 for a, n in enumerate(shape))
    pos = first + lax.broadcasted_iota(jnp.int32, vec, axis)
    shift = block.bit_length() - 1
    return pos >> shift if block == 1 << shift else pos // block


def _band_mask(rule: BlockDiffusionMask, band, q_axis: int, shape):
    """The allowed pairs of a masked tile of ``shape`` whose queries lie
    along ``q_axis``: the block of each query (a column or a row vector),
    of each key (the other), and the band between them."""
    q0, k0, c1, c2 = band
    gap = _blocks_along(k0, 1 - q_axis, shape, rule.block) \
        - _blocks_along(q0, q_axis, shape, rule.block)      # kb - qb
    return (gap <= -c1) & (gap >= -c2)


# ---------------------------------------------------------------------------
# the third static rule: a band below the diagonal
# ---------------------------------------------------------------------------

#: under a ``WindowMask``: the candidate (square) tiles of either pass,
#: largest first; ``WindowMask.tiles`` chooses among them (PERF.md section
#: 6, PR 41: the sweep at 8,192 positions and a window of 512, where 512 x
#: 512 won both passes)
WINDOW_TILES = (512, 256, 128)


def _floor0(x):
    """``max(x, 0)`` of a Python or a traced integer."""
    return max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)


@dataclasses.dataclass(frozen=True)
class WindowMask:
    """Sliding-window attention, the third static rule beside ``causal``
    (which it implies) and ``BlockDiffusionMask``: query ``t`` sees the
    ``window`` keys ``t - window < s <= t``, its own among them.  Of the
    causal triangle only a band along the diagonal is needed (at 8,192
    positions and a window of 512 an eighth), so the kernels do not step
    over the whole other axis and skip: **the forward's key axis and the
    backward's query axis span only the tiles a band can touch**
    (``key_steps``, ``query_steps``: with square tiles of ``b``, ``ceil((W -
    1) / b) + 1`` of them), and the index maps add the tile's own offset
    (``key_tile``, ``query_tile``).  Query tile ``qi`` touches the key tiles
    ``key_span(qi)``; its steps are the last ``key_steps`` tiles up to its
    diagonal tile, so near the sequence's start the first steps fall before
    the span: they compute nothing and name the span's first tile, which the
    next step that runs fetches anyway.  The backward's key tile ``ki``
    steps from its diagonal tile on; steps past ``query_span(ki)`` (at the
    sequence's end) compute nothing and name the span's last tile again.  A
    tile runs unmasked where every pair of it is in the band, else under
    both edges (``_in_band``).  Static: fates and fetches follow from
    offsets, as for the other rules.  The methods take Python or traced
    integers; ``key_steps``, ``query_steps``, ``tiles_run``,
    ``causal_tiles`` and ``pairs`` are sums of Python integers over one
    axis' tiles (no array is made at trace time)."""
    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window of {self.window} keys")

    def allowed(self, q_pos, k_pos):
        """The rule, element by element: what a dense masked softmax
        applies (the kernels' oracle)."""
        return (k_pos <= q_pos) & (k_pos > q_pos - self.window)

    def tiles(self, s: int):
        """The (square) tile of either pass over ``s`` positions: the
        largest of ``WINDOW_TILES`` that divides ``s`` and is no wider than
        the window rounded up to a lane tile (a wider one computes more
        outside the band than inside it)."""
        cap = -(-self.window // _LANES) * _LANES
        got = [t for t in WINDOW_TILES if s % t == 0 and t <= cap]
        if not got:
            raise ValueError(f"seq length {s} must be a multiple of "
                             f"{WINDOW_TILES[-1]}")
        return got[0], got[0]

    def key_span(self, qi, block_q: int, block_k: int):
        """First and last key tile that query tile ``qi`` needs."""
        return _floor0(qi * block_q - self.window + 1) // block_k, \
            (qi * block_q + block_q - 1) // block_k

    def query_span(self, ki, block_q: int, block_k: int, n_q: int):
        """First and last query tile that key tile ``ki`` is needed by."""
        last = (ki * block_k + block_k + self.window - 2) // block_q
        return ki * block_k // block_q, (
            min(last, n_q - 1) if isinstance(last, int)
            else jnp.minimum(last, n_q - 1))

    def key_steps(self, s: int, block_q: int, block_k: int) -> int:
        """The forward's grid along the keys: the most key tiles a query
        tile needs."""
        return max(hi - lo + 1 for lo, hi in (
            self.key_span(qi, block_q, block_k)
            for qi in range(s // block_q)))

    def query_steps(self, s: int, block_q: int, block_k: int) -> int:
        """The backward's grid along the queries, as ``key_steps``."""
        n_q = s // block_q
        return max(hi - lo + 1 for lo, hi in (
            self.query_span(ki, block_q, block_k, n_q)
            for ki in range(s // block_k)))

    def key_tile(self, qi, step, steps: int, block_q: int, block_k: int):
        """The key tile of the forward's step ``step`` of ``steps`` for
        query tile ``qi``: the last ``steps`` tiles up to the diagonal
        one's; below ``key_span``'s first (or 0) the step does not run."""
        return self.key_span(qi, block_q, block_k)[1] - (steps - 1) + step

    def query_tile(self, ki, step, block_q: int, block_k: int):
        """The query tile of the backward's step ``step`` for key tile
        ``ki``: from the diagonal one's on; past ``query_span``'s last the
        step does not run."""
        return ki * block_k // block_q + step

    def tiles_run(self, s: int, block_q: int, block_k: int) -> int:
        """How many tiles run in a pass over ``s`` positions (either pass:
        the same tiles, walked the other way)."""
        return sum(hi - lo + 1 for lo, hi in (
            self.key_span(qi, block_q, block_k)
            for qi in range(s // block_q)))

    @staticmethod
    def causal_tiles(s: int, block_q: int, block_k: int) -> int:
        """The tiles ``causal`` alone would run with the same tiles."""
        return sum((qi * block_q + block_q - 1) // block_k + 1
                   for qi in range(s // block_q))

    def pairs(self, s: int) -> int:
        """The pairs one sequence of ``s`` positions needs: ``sum_t min(t +
        1, window)``."""
        w = min(s, self.window)
        return w * (w + 1) // 2 + (s - w) * w


def _in_band(shape, q_axis: int, gap, window: int):
    """Of a tile of ``shape`` whose queries lie along ``q_axis``: the pairs
    whose key is at or before the query and less than ``window`` before it,
    ``gap`` the tile's first key position less its first query position."""
    ahead = lax.broadcasted_iota(jnp.int32, shape, q_axis) \
        - lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return (ahead >= gap) & (ahead < gap + window)


def _when_window(tile, rule: WindowMask, valid, qi, ki, block_q: int,
                 block_k: int):
    """Run ``tile(masked)`` for the (qi, ki) tile under ``rule``: not at
    all where ``valid`` is false (a step outside the grid's span) or no pair
    of it lies in the band, unmasked where all do."""
    first_k, last_k = ki * block_k, ki * block_k + block_k - 1
    first_q, last_q = qi * block_q, qi * block_q + block_q - 1
    runs = valid & (first_k <= last_q) & (last_k > first_q - rule.window)
    unmasked = (last_k <= first_q) & (first_k > last_q - rule.window)
    pl.when(runs & jnp.logical_not(unmasked))(functools.partial(tile, True))
    pl.when(runs & unmasked)(functools.partial(tile, False))


def _when_causal(tile, qi, ki, block_q: int, block_k: int):
    """Run ``tile(masked)`` for the (qi, ki) tile under the causal mask: not
    at all where the tile's first key position is beyond its last query
    position (no products and, by the index maps, no fetch), masked where
    the diagonal crosses it, unmasked below."""
    first_k, last_k = ki * block_k, ki * block_k + block_k - 1
    first_q, last_q = qi * block_q, qi * block_q + block_q - 1
    runs = first_k <= last_q
    crossed = last_k > first_q
    pl.when(runs & crossed)(functools.partial(tile, True))
    pl.when(runs & jnp.logical_not(crossed))(functools.partial(tile, False))


# ---------------------------------------------------------------------------
# the rule that is data: a selection of keys for each query
# ---------------------------------------------------------------------------

#: keys one row of 128 int32 words stands for: 32 bits x 128 lanes
SEL_GROUP = 32 * _LANES


class Selection(NamedTuple):
    """Which keys each query may see, as the kernels read it (``pack_rows``
    and ``pack_columns`` make the bitmaps chunk by chunk, ``pack_selection``
    from a whole boolean array):

    ``by_query`` (B, G, T, 128) int32, ``G = ceil(T / 4096)``: bit ``b`` of
    ``[n, g, t, l]`` is set where query ``t`` sees key ``g * 4096 + b * 128 +
    l``; ``by_key`` (B, G, T, 128): bit ``b`` of ``[n, g, s, l]`` where key
    ``s`` is seen by query ``g * 4096 + b * 128 + l`` (the backward's tile
    lies keys by queries); ``blocks`` (B, T / 128, T / 128) bool: which
    128-query by 128-key blocks hold a selected pair."""
    by_query: jax.Array
    by_key: jax.Array
    blocks: jax.Array


def pack_rows(allowed):
    """``allowed`` (..., R, T) bool, ``R`` rows against all ``T`` positions
    of the other side -> (..., G, R, 128) int32 in the ``Selection``'s
    layout: the rows are queries and the packed side keys for ``by_query``,
    the other way round for ``by_key``."""
    *lead, r, t = allowed.shape
    g = -(-t // SEL_GROUP)
    a = jnp.pad(allowed, [(0, 0)] * (len(lead) + 1)
                + [(0, g * SEL_GROUP - t)])
    a = a.reshape(*lead, r, g, 32, _LANES).astype(jnp.uint32)
    words = jnp.sum(a << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2,
                    dtype=jnp.uint32)
    return jnp.moveaxis(lax.bitcast_convert_type(words, jnp.int32), -2, -3)


def pack_columns(allowed, first: int):
    """``allowed`` (..., R, C) bool, the ``C`` positions from ``first`` of
    the packed side (whole 128s, inside one group) -> (..., R, 128) int32:
    their bits of the group's words, the other bits 0, to be OR-ed into the
    group ``first // SEL_GROUP``."""
    *lead, r, c = allowed.shape
    if c % _LANES or first % _LANES or \
            first // SEL_GROUP != (first + c - 1) // SEL_GROUP:
        raise ValueError(f"{c} positions from {first} are not whole 128s "
                         f"inside one group of {SEL_GROUP}")
    a = allowed.reshape(*lead, r, c // _LANES, _LANES).astype(jnp.uint32)
    bit = (first % SEL_GROUP) // _LANES \
        + jnp.arange(c // _LANES, dtype=jnp.uint32)
    return lax.bitcast_convert_type(
        jnp.sum(a << bit[:, None], axis=-2, dtype=jnp.uint32), jnp.int32)


def _bits(words):
    """(..., 128) int32 words -> (..., 32, 128) int32: bit ``b`` of each."""
    return (words[..., None, :] >> jnp.arange(32)[:, None]) & 1


def selected_blocks(by_query):
    """``by_query`` (B, G, T, 128) -> (B, T / 128, T / 128) bool: the 128 x
    128 blocks that hold a selected pair (a block's words OR-ed over its
    128 queries and 128 lanes first, then one word's 32 bits)."""
    b, g, t, _ = by_query.shape
    any_of = lax.reduce(by_query.reshape(b, g, t // _LANES, _LANES * _LANES),
                        jnp.int32(0), lax.bitwise_or, (3,))
    cols = (any_of[..., None] >> jnp.arange(32)) & 1    # (B, G, T / 128, 32)
    return jnp.moveaxis(cols, 1, 2).reshape(
        b, t // _LANES, g * 32)[..., :t // _LANES] != 0


def pack_selection(allowed) -> Selection:
    """``allowed`` (B, T, T) bool (query, key) -> the ``Selection``; ``T`` a
    multiple of 128.  For tests and small sizes: the indexer packs chunk by
    chunk and never holds the square (``ops/sparse_index.py``)."""
    by_query = pack_rows(allowed)
    return Selection(by_query, pack_rows(jnp.swapaxes(allowed, -1, -2)),
                     selected_blocks(by_query))


def unpack_selection(by_query, t: int):
    """``by_query`` (B, G, T, 128) -> (B, T, T) bool (query, key): the dense
    oracle's mask.  Small sizes only."""
    b, g = by_query.shape[:2]
    return jnp.moveaxis(_bits(by_query), 1, 2).reshape(
        b, t, g * SEL_GROUP)[..., :t] != 0


def unpack_tile(words, first, n: int):
    """``words`` (R, 128) int32 of one group -> (R, n) int32, 1 where the
    position ``first + c`` of the packed side is selected (``first`` inside
    the group, a traced or Python integer; ``n`` whole 128s)."""
    bit0 = (first % SEL_GROUP) // _LANES
    return jnp.concatenate([(words >> (bit0 + m)) & 1
                            for m in range(n // _LANES)], axis=1)


@dataclasses.dataclass(frozen=True)
class SelectedKeysMask:
    """The rule that is data: query ``t`` sees the keys a ``Selection``
    names for it (``flash_attention(..., mask=SelectedKeysMask(),
    selection=...)``), beside ``causal`` or without it.  Static in kind
    only: the kernels it selects take the bitmaps and a table of tile fates
    as operands.  Every query must see at least one key (under ``causal``
    an indexer's selection holds the query's own position or an earlier
    one): a row without any would read an average of the values."""

    @staticmethod
    def tile_fates(blocks, block_q: int, block_k: int, by_key: bool = False):
        """``blocks`` (B, T / 128, T / 128) -> (B, n_q, n_k) int32 (``by_key``:
        (B, n_k, n_q)): 1 where the tile holds a selected pair."""
        b, nq, nk = blocks.shape
        rq, rk = block_q // _LANES, block_k // _LANES
        fates = jnp.any(blocks.reshape(b, nq // rq, rq, nk // rk, rk),
                        axis=(2, 4))
        return (jnp.swapaxes(fates, 1, 2) if by_key else fates).astype(
            jnp.int32)

    @staticmethod
    def tiles(blocks, block_q: int, block_k: int, causal: bool):
        """(tiles that run, tiles ``causal`` alone would run) for each row of
        the batch, (B,) int32 each: what the counters report."""
        fates = SelectedKeysMask.tile_fates(blocks, block_q, block_k) != 0
        nq, nk = fates.shape[1:]
        below = jnp.ones((nq, nk), bool) if not causal else (
            jnp.arange(nk)[None, :] * block_k
            <= jnp.arange(nq)[:, None] * block_q + block_q - 1)
        return jnp.sum(fates & below, axis=(1, 2), dtype=jnp.int32), \
            jnp.full((fates.shape[0],), jnp.sum(below), jnp.int32)


def _at_or_before(shape, q_axis: int, gap):
    """Of a tile of ``shape`` whose queries lie along ``q_axis``: the pairs
    whose key is at or before the query, ``gap`` the tile's first key
    position less its first query position."""
    return lax.broadcasted_iota(jnp.int32, shape, q_axis) \
        - lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) >= gap


def _when_selected(tile, fate, causal: bool, qi, ki, block_q: int,
                   block_k: int):
    """Run ``tile(masked)`` for the (qi, ki) tile under a selection: not at
    all where its ``fate`` is 0 or, under ``causal``, where it lies above
    the diagonal; ``masked`` says whether the diagonal crosses it (the
    bitmap is applied either way)."""
    runs = fate != 0
    if not causal:
        pl.when(runs)(functools.partial(tile, False))
        return
    runs = runs & (ki * block_k <= qi * block_q + block_q - 1)
    crossed = ki * block_k + block_k - 1 > qi * block_q
    pl.when(runs & crossed)(functools.partial(tile, True))
    pl.when(runs & jnp.logical_not(crossed))(functools.partial(tile, False))


# ---------------------------------------------------------------------------
# inside a crossed tile: only the sub-blocks that hold an allowed pair
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Edge:
    """One kind of crossed tile (a tile an edge of the mask crosses) whose
    pattern is static.  With ``q`` and ``k`` counted from the tile's first
    query and first key, a pair is allowed where

        lo <= (k + gap) // unit - q // unit <= hi

    ``unit`` 1 and ``gap`` the tile's first key position less its first
    query position under ``causal`` and a band; ``unit`` the rule's block
    and ``gap`` 0 under ``BlockDiffusionMask`` (the crossed tiles are the
    quadrants' diagonal ones, which start on a block).  The difference
    takes every value between its least and its largest over a sub-block,
    so a sub-block's fate follows from its corners, as a tile's does
    (``BlockDiffusionMask.tile``); all Python integers."""
    gap: int
    lo: int
    hi: int
    unit: int = 1

    def reach(self, q0: int, k0: int, n: int):
        """Least and largest difference over the ``n x n`` sub-block at
        query ``q0``, key ``k0``."""
        return ((k0 + self.gap) // self.unit - (q0 + n - 1) // self.unit,
                (k0 + n - 1 + self.gap) // self.unit - q0 // self.unit)

    def fate(self, q0: int, k0: int, n: int) -> int:
        """Of that sub-block: 0 no pair allowed, 2 all, 1 the edge passes
        through it."""
        least, most = self.reach(q0, k0, n)
        if most < self.lo or least > self.hi:
            return 0
        return 2 if self.lo <= least and most <= self.hi else 1

    def spans(self, block: int, sub: int, by_key: bool = False):
        """The walk of a ``block x block`` tile in sub-blocks of ``sub``:
        for each row block of queries (``by_key``: of keys, the backward's
        tile lies keys by queries) ``(first, last + 1, fates)``, the
        sub-blocks of the other side that hold an allowed pair (one run:
        the rules are bands) and their fates."""
        n = block // sub
        walk = []
        for i in range(n):
            fates = [self.fate(j * sub, i * sub, sub) if by_key
                     else self.fate(i * sub, j * sub, sub) for j in range(n)]
            held = [j for j in range(n) if fates[j]]
            lo, hi = (held[0], held[-1] + 1) if held else (0, 0)
            assert len(held) == hi - lo, (self, fates)
            walk.append((lo, hi, tuple(fates[lo:hi])))
        return walk

    def sub_blocks(self, block: int, sub: int) -> int:
        """How many of the tile's ``(block / sub)^2`` sub-blocks the walk
        computes."""
        return sum(hi - lo for lo, hi, _ in self.spans(block, sub))

    def allowed(self, q0: int, k0: int, n: int, q_axis: int):
        """The allowed pairs of the crossed ``n x n`` sub-block at query
        ``q0``, key ``k0``, queries along ``q_axis``: one compare where one
        bound alone can fail inside it."""
        shape = (n, n)
        diff = _blocks_along(k0 + self.gap, 1 - q_axis, shape, self.unit) \
            - _blocks_along(q0, q_axis, shape, self.unit)
        least, most = self.reach(q0, k0, n)
        if self.lo == self.hi:
            return diff == self.lo
        if least >= self.lo:
            return diff <= self.hi
        if most <= self.hi:
            return diff >= self.lo
        return (diff >= self.lo) & (diff <= self.hi)

    def mask_span(self, s, q0: int, k0: int, sub: int, fates, q_axis: int):
        """``s``, the scores of one row block against a run of sub-blocks
        of the other side (along axis 1) from query ``q0``, key ``k0``:
        ``NEG_INF`` outside the rule in the sub-blocks whose fate is 1, the
        others as they are."""
        if 1 not in fates:
            return s
        parts, j = [], 0
        while j < len(fates):
            end = j + 1
            while fates[j] == 2 and end < len(fates) and fates[end] == 2:
                end += 1
            part = s if end - j == len(fates) else s[:, j * sub:end * sub]
            if fates[j] == 1:
                at = (q0 + j * sub, k0) if q_axis else (q0, k0 + j * sub)
                part = jnp.where(self.allowed(*at, sub, q_axis), part,
                                 NEG_INF)
            parts.append(part)
            j = end
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


class _Crossed(NamedTuple):
    """One kind of crossed tile in a grid: its ``edge``, ``here(qi, ki)``
    (whether a crossed tile is of this kind: a traced boolean, or True
    where the grid has one kind) and how many ``tiles`` of the grid are."""
    edge: _Edge
    here: object
    tiles: int


def crossed_kinds(mask, causal: bool, s: int, sk: int, block_q: int,
                  block_k: int, sub: int):
    """The kinds of crossed tile of a grid, or None where the walk is not
    taken (every tile that runs is then computed whole): the tiles are
    square and whole sub-blocks of ``sub``, and every crossed tile lies on
    its edge the same way.  That is so under ``causal`` (the diagonal
    tiles); under a ``WindowMask`` whose window is whole tiles (the diagonal
    tiles meet the causal edge alone, the tiles a window before them the
    band's edge alone); under a ``BlockDiffusionMask`` whose block divides
    the tile (the three quadrants' diagonal tiles).  A caller's rectangular
    tiles, a window or a block that is not a whole part of a tile, a call
    without ``causal`` or a rule: None."""
    b = block_q
    if not sub or block_q != block_k or b % sub or b == sub:
        return None
    n_diag = min(s // b, sk // b)
    if isinstance(mask, WindowMask):
        if mask.window % b:
            return None
        away = mask.window // b
        band = dict(lo=1 - mask.window, hi=0)
        return (_Crossed(_Edge(0, **band), lambda qi, ki: ki == qi, n_diag),
                _Crossed(_Edge(-mask.window, **band),
                         lambda qi, ki: ki != qi, max(n_diag - away, 0)))
    if isinstance(mask, BlockDiffusionMask):
        if b % mask.block or b == mask.block:
            return None
        n, far = mask.half // b, mask._FAR
        return (
            _Crossed(_Edge(0, 0, 0, mask.block),            # the diagonal
                     lambda qi, ki: ki < n, n),
            _Crossed(_Edge(0, -far, -1, mask.block),        # kb < qb
                     lambda qi, ki: (qi < n) & (ki >= n), n),
            _Crossed(_Edge(0, -far, 0, mask.block),         # kb <= qb
                     lambda qi, ki: qi >= n, n))
    if causal:      # alone, or beside a selection
        return (_Crossed(_Edge(0, -BlockDiffusionMask._FAR, 0),
                         lambda qi, ki: True, n_diag),)
    return None


def _run_tile(kinds, whole, walk, qi, ki, masked):
    """A tile that runs: ``whole(masked)``, or where an edge crosses it
    (``masked``) and the grid's crossed tiles have a static pattern
    (``kinds``), ``walk(edge)`` of its kind."""
    if kinds is None or not masked:
        return whole(masked)
    for kind in kinds:
        pl.when(kind.here(qi, ki))(functools.partial(walk, kind.edge))


def computed_tiles(mask, causal: bool, s: int, sk: int, block_q: int,
                   block_k: int, sub: int):
    """``(run, crossed, computed)`` for one head's grid: the tiles that
    run, how many of them the walk takes sub-block by sub-block (0: it is
    not taken), and the tile-equivalents computed in all, the crossed
    tiles counted by their sub-blocks.  Under a selection the tiles that
    run are data: the sums are then ``causal``'s (all of its tiles run
    with seeded weights), or None without ``causal``.  Python integers
    (``BlockDiffusionMask.tiles_run`` apart)."""
    n_q, n_k = s // block_q, sk // block_k
    if isinstance(mask, BlockDiffusionMask):
        run = mask.tiles_run(block_q, block_k)
    elif isinstance(mask, WindowMask):
        run = mask.tiles_run(s, block_q, block_k)
    elif causal:
        run = sum(min((qi * block_q + block_q - 1) // block_k + 1, n_k)
                  for qi in range(n_q))
    elif mask is None:
        run = n_q * n_k
    else:
        return None
    kinds = crossed_kinds(mask, causal, s, sk, block_q, block_k, sub) or ()
    crossed = sum(kind.tiles for kind in kinds)
    part = sum(kind.tiles * kind.edge.sub_blocks(block_q, sub)
               for kind in kinds) / (block_q // sub) ** 2 if kinds else 0
    return run, crossed, run - crossed + part


def _kernel_name(mask, which: str) -> str:
    """The name a kernel's events carry in a trace under a mask rule.  The
    band's hold neither ``flash_fwd`` nor ``flash_bwd``, so that a metric
    that reads the plain causal kernels beside them (a decoder that
    alternates the two kinds of layer) counts those alone."""
    if isinstance(mask, WindowMask):
        return f"flash_win_{which}"
    return f"flash_{which}_" + (
        "sel" if isinstance(mask, SelectedKeysMask) else "bd")


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 n_k: int, mask=None, sel=None, kinds=None, sub: int = 0):
    """One (bh, q_block, k_block) grid step; kv axis is sequential, so the
    VMEM scratch (acc, m, l) carries the online softmax across it.  ``n_k``
    is that axis' steps: the key tiles, or under a ``WindowMask`` the tiles
    a band can touch, the step's key tile then being ``mask.key_tile``'s.
    ``sel`` (under a ``SelectedKeysMask``) is ``(fate, words)``: the tile's
    entry of the fate table and the ref of its queries' bitmap block.
    ``kinds`` (``crossed_kinds``) are the grid's kinds of crossed tile,
    walked in sub-blocks of ``sub``; None: a crossed tile is computed and
    masked whole."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qi = pl.program_id(1)
    windowed = isinstance(mask, WindowMask)
    ki = mask.key_tile(qi, step, n_k, block_q, block_k) if windowed else step

    def _attend(masked):
        # ``masked``: False, True (the causal diagonal) or a band of
        # ``mask``.  Operands as stored: the MXU takes bfloat16 at full rate
        # and accumulates float32; float32 inputs multiply as before
        q, k, v = q_ref[0], k_ref[0], v_ref[0]        # (BQ, D), (BK, D) x2
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if sel is not None:
            seen = unpack_tile(sel[1][0, 0], ki * block_k, block_k) != 0
            if masked:
                seen = seen & _at_or_before(s.shape, 0,
                                            ki * block_k - qi * block_q)
            s = jnp.where(seen, s, NEG_INF)
        elif windowed:
            if masked:
                s = jnp.where(_in_band(s.shape, 0, ki * block_k
                                       - qi * block_q, mask.window), s,
                              NEG_INF)
        elif mask is not None:
            if masked is not None:
                s = jnp.where(_band_mask(mask, masked, 0, s.shape), s,
                              NEG_INF)
        elif masked:
            # q_pos >= k_pos, the tile's offsets moved to the scalar side
            row_less_col = lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) - lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row_less_col >= ki * block_k - qi * block_q,
                          s, NEG_INF)

        _update(slice(None), s, v)

    def _update(rows, s, v):
        # the online softmax of the query rows ``rows`` over the scores
        # ``s`` of some of the tile's keys and their values ``v``
        m_prev = m_ref[rows]                          # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (BQ, BK)
        correction = jnp.exp(m_prev - m_new)          # (BQ, 1)
        l_ref[rows] = l_ref[rows] * correction + p.sum(axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    def _walk(edge):
        # a crossed tile of the kind ``edge``: for each row block of
        # queries the product against the key sub-blocks that hold an
        # allowed pair, masked only where the edge passes, and the online
        # softmax of those rows alone.  Static slices, unrolled; every row
        # block's scores before the first softmax, so that one's softmax
        # and the next one's products overlap (PERF.md section 6, PR 42:
        # row block after row block the same walk took a quarter longer)
        blocks, scores = [], []
        for r, (lo, hi, fates) in enumerate(edge.spans(block_q, sub)):
            if lo == hi:
                continue
            rows, keys = slice(r * sub, (r + 1) * sub), \
                slice(lo * sub, hi * sub)
            s = jax.lax.dot_general(
                q_ref[0, rows], k_ref[0, keys], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = edge.mask_span(s, r * sub, lo * sub, sub, fates, 0)
            if sel is not None:
                seen = unpack_tile(sel[1][0, 0, rows],
                                   ki * block_k + lo * sub, (hi - lo) * sub)
                s = jnp.where(seen != 0, s, NEG_INF)
            blocks.append((rows, keys))
            scores.append(s)
        for (rows, keys), s in zip(blocks, scores):
            _update(rows, s, v_ref[0, keys])

    _tile = functools.partial(_run_tile, kinds, _attend, _walk, qi, ki)

    if sel is not None:
        _when_selected(_tile, sel[0], causal, qi, ki, block_q, block_k)
    elif windowed:
        _when_window(_tile, mask, ki >= 0, qi, ki, block_q, block_k)
    elif mask is not None:
        _when_block_diffusion(_tile, mask, qi, ki, block_q, block_k)
    elif causal:
        _when_causal(_tile, qi, ki, block_q, block_k)
    else:
        _attend(False)

    @pl.when(step == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # the log-sum-exp is a column (BQ, 1); Mosaic stores lane tiles,
        # so row r goes to [r // 128, r % 128] of a (BQ/128, 128) tile:
        # the column spread over the lanes, its diagonal kept, each 128
        # rows summed into one
        lse = m_ref[:] + jnp.log(l)
        rows = lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 0)
        lanes = lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 1)
        spread = jnp.where(rows % _LANES == lanes, lse, 0.0)
        lse_ref[0, 0] = spread.reshape(
            block_q // _LANES, _LANES, _LANES).sum(axis=1)


def _attn_kernel_sel(fate_ref, q_ref, k_ref, v_ref, words_ref, *rest,
                     heads: int, **kw):
    """``_attn_kernel`` under a ``SelectedKeysMask``: the fate table comes
    first (scalar prefetch), the queries' bitmap block after v."""
    fate = fate_ref[pl.program_id(0) // heads, pl.program_id(1),
                    pl.program_id(2)]
    _attn_kernel(q_ref, k_ref, v_ref, *rest, sel=(fate, words_ref), **kw)


def _kv_row(b, rep: int):
    """The key-value row that query row ``b`` of (B * H) reads where a
    key-value head serves ``rep`` consecutive query heads: row ``b // rep``
    of (B * KV).  At ``rep`` 1 the index as it came, so that a call without
    groups lowers to what it did."""
    return b if rep == 1 else b // rep


def _head_at(heads: Optional[int], rep: int = 1):
    """Where grid row ``b = batch * H + head`` finds its head in an operand:
    ``b -> (row, column block)``, the first and the last entry of a block
    index whose middle one is the tile.  ``heads`` None: the operand is (B *
    H, S, D), heads after the batch, so row ``b`` (``_kv_row`` of it in an
    operand of key-value heads) and column block 0.  ``heads = H``: the
    operand is (B, S, H * D) as a projection leaves it, so row ``b // H``
    and the head's own ``D`` columns, block ``b % H`` (``// rep`` in an
    operand of key-value heads, (B, SK, KV * D))."""
    if heads is None:
        return lambda b: (_kv_row(b, rep), 0)
    return lambda b: (b // heads, _kv_row(b % heads, rep))


def lane_tiled(d: int, *lengths: int) -> bool:
    """Whether heads of ``d`` over ``lengths`` positions can be read where a
    projection leaves them: a head whole tiles of 128 lanes, the positions
    whole tiles of 8 sublanes (``by_head``).  The one place that knows it:
    ``flash_attention`` takes (B, S, H * D) operands where it holds, and a
    layer asks it before it keeps its own work on those arrays."""
    return d % _LANES == 0 and not any(n % 8 for n in lengths)


def by_head(x3, heads: int):
    """(B, S, H * D) -> (B, S / 8, H, 8, D): each head's ``D`` columns of
    eight positions together, which is how the (8, 128) tiles of the (B, S,
    H * D) array lie in memory, so XLA reads the view where the array is; a
    (B, S, H, D) view would be tiled over (H, D) and cost a pass over the
    array.  ``from_heads`` is the way back."""
    b, s, width = x3.shape
    return x3.reshape(b, s // 8, 8, heads, width // heads).transpose(
        0, 1, 3, 2, 4)


def from_heads(x5):
    """(B, S / 8, H, 8, D) -> (B, S, H * D): ``by_head`` undone."""
    b, s8, heads, _, d = x5.shape
    return x5.transpose(0, 1, 3, 2, 4).reshape(b, s8 * 8, heads * d)


def _rows_of(q3, k3, heads: Optional[int]):
    """``(B * H, S, D, rep)`` of a call's operands: q (B * H, S, D) beside
    k (B * KV, SK, D), or with ``heads = H`` q (B, S, H * D) beside k (B,
    SK, KV * D)."""
    if heads is None:
        bh, s, d = q3.shape
        return bh, s, d, bh // k3.shape[0]
    b, s, width = q3.shape
    return b * heads, s, width // heads, width // k3.shape[2]


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "mask", "sub",
    "heads"))
def _flash_fwd_pallas(q3, k3, v3, *, scale, causal, block_q, block_k,
                      interpret, mask=None, selection=None, sub=None,
                      heads=None):
    """(BH, S, D) q/k/v -> (out (BH, S, D), lse (BH, S)).  ``block_q`` /
    ``block_k`` of None are derived from the shapes (``forward_tiles``).
    ``selection`` is the ``Selection`` a ``SelectedKeysMask`` reads.
    ``sub`` is the side of a crossed tile's sub-blocks (None:
    ``SUB_BLOCK``; 0: crossed tiles computed whole), for the sweep and the
    tests: no caller in the package gives it.  ``k3`` / ``v3`` may hold
    fewer rows than ``q3``, (B * KV, SK, D) for (B * H, S, D): query row
    ``b`` then reads key-value row ``b // rep``, ``rep = H // KV``.

    With ``heads = H`` the operands are in the layout a projection leaves
    them in, q (B, S, H * D) and k, v (B, SK, KV * D), ``D`` whole lane
    tiles: the same grid over (B * H) rows, each block the ``D`` columns of
    the row's head (``_head_at``), and ``out`` comes back (B, S, H * D);
    the log-sum-exp is (B * H, S) either way.

    Jitted and inlined: a model's layers share one trace of the kernel's
    body (Pallas traces it anew for every call otherwise, 24 times a
    build of the gpt2-medium step), and the call keeps its caller's scope
    and so its event's name."""
    bh, s, d, rep = _rows_of(q3, k3, heads)
    sk = k3.shape[1]
    selected = isinstance(mask, SelectedKeysMask)
    windowed = isinstance(mask, WindowMask)
    if block_q is None or block_k is None:
        dq, dk = forward_tiles(s, sk, d, q3.dtype.itemsize,
                               None if selected else mask)
        block_q, block_k = block_q or dq, block_k or dk
    sub = SUB_BLOCK if sub is None else sub
    _note_tiles((s, sk, d, q3.dtype.name), block_q, block_k, mask=mask,
                causal=causal, sub=sub, rep=rep, bshd=heads is not None)
    n_q = s // block_q
    # the key axis' steps: under a band, the tiles it can touch
    n_k = mask.key_steps(s, block_q, block_k) if windowed else sk // block_k
    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, mask=mask, sub=sub,
        kinds=crossed_kinds(mask, causal, s, sk, block_q, block_k, sub))
    # the index maps take the grid's indices and, under a selection, the
    # scalar-prefetch table after them
    if windowed:
        # a step before the band names the band's first tile, which the
        # first step that runs fetches anyway
        key_tile = lambda qi, step: jnp.maximum(
            mask.key_tile(qi, step, n_k, block_q, block_k),
            mask.key_span(qi, block_q, block_k)[0])
    elif mask is not None and not selected:
        key_tile = lambda qi, ki: mask.key_tile(qi, ki, block_q, block_k)
    elif causal:
        # a skipped step names the last block its query tile needs: the
        # same block as the step before, so nothing is fetched for it
        key_tile = lambda qi, ki: jnp.minimum(
            ki, (qi * block_q + block_q - 1) // block_k)
    else:
        key_tile = lambda qi, ki: ki
    # the key-value head of query row b = batch * H + head: nothing spreads
    # the heads in memory, a group's query heads fetch the same block
    q_at, kv_at = _head_at(heads), _head_at(heads, rep)

    def kv_map(b, qi, ki, *_):
        row, col = kv_at(b)
        return row, key_tile(qi, ki), col

    def q_map(b, qi, ki, *_):
        row, col = q_at(b)
        return row, qi, col
    sub = block_q // _LANES
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, 1, sub, _LANES), lambda b, qi, ki, *_: (b, qi, 0, 0)),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
    ]
    operands = (q3, k3, v3)
    if selected:
        # the queries' bitmap block of the key tile's group: one fetch for
        # the SEL_GROUP / block_k key tiles that share it
        sel_heads = bh // selection.by_query.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q, _LANES), lambda b, qi, ki, *_: (
                b // sel_heads, key_tile(qi, ki) * block_k // SEL_GROUP, qi,
                0)))
        kern = functools.partial(_attn_kernel_sel, heads=sel_heads,
                                 **kern.keywords)
        operands = (SelectedKeysMask.tile_fates(selection.blocks, block_q,
                                                block_k),
                    q3, k3, v3, selection.by_query)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, n_q, n_k), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes))
    else:
        grid = dict(grid=(bh, n_q, n_k), in_specs=in_specs,
                    out_specs=out_specs, scratch_shapes=scratch_shapes)
    out, lse = pl.pallas_call(
        kern,
        # under a mask rule the forward has a name of its own in a trace;
        # otherwise its events carry its caller's, as they always have
        **({} if mask is None else {"name": _kernel_name(mask, "fwd")}),
        **grid,
        out_shape=[
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct((bh, n_q, sub, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * VMEM_BUDGET),
        interpret=interpret,
    )(*operands)
    return out, lse.reshape(bh, s)


@functools.lru_cache(maxsize=None)
def _note_tiles(shape, block_q: int, block_k: int, bwd: bool = False,
                mask=None, causal: bool = False, sub: int = 0,
                rep: int = 1, bshd: bool = False) -> None:
    """Record, once per distinct shape, tile and mask rule, what the forward
    (or with ``bwd`` the backward) was traced with: a ``# flash_tiles`` (``#
    flash_bwd_tiles``) debug line and the metrics plane's gauges, so that a
    shape that falls back to 128 is seen.  Under a mask rule or ``causal``
    the line and the gauges' label ``mask`` name it, with the tiles that
    run of the grid's and, where crossed tiles are walked in sub-blocks of
    ``sub``, how many they are and the tile-equivalents computed in all
    (``computed_tiles``); the gauge ``flash.pairs_computed_pct``
    (``flash.bwd_pairs_computed_pct``) is that over the tiles that run, 100
    where every tile is computed whole.  Where the keys and values came
    with fewer heads than the queries the line ends in ``rep=<query heads a
    key-value head>`` and the gauges carry the label ``rep``: the index maps
    read the group's head, nothing was spread.  Where the operands came as
    a projection leaves them, (B, S, H * D) (``bshd``: head sizes of whole
    lane tiles), the line ends in ``layout=bshd`` and the gauges carry the
    label ``layout``.  Trace time only."""
    s, sk, d, dtype = shape
    if isinstance(mask, BlockDiffusionMask):
        rule = f"block_diffusion.half{mask.half}.block{mask.block}"
    elif isinstance(mask, WindowMask):
        rule = f"window{mask.window}"
    elif isinstance(mask, SelectedKeysMask):
        # a selection's tiles are data: the model's counters have them
        rule = "selected_keys" + (".causal" if causal else "")
    else:
        rule = "causal" if causal else ""
    sums = computed_tiles(mask, causal, s, sk, block_q, block_k, sub)
    pct = 100.0
    if sums is not None and rule:
        run, crossed, computed = sums
        rule += f".run{run}of{(s // block_q) * (sk // block_k)}"
        if crossed:
            rule += f".crossed{crossed}.sub{round(computed, 4)}of{run}"
            pct = 100.0 * computed / run
    logger.debug("# flash_%stiles s=%d sk=%d d=%d dtype=%s block_q=%d "
                 "block_k=%d%s%s%s", "bwd_" if bwd else "", s, sk, d, dtype,
                 block_q, block_k, " mask=" + rule if rule else "",
                 f" rep={rep}" if rep > 1 else "",
                 " layout=bshd" if bshd else "")
    if obs_metrics.enabled():
        reg = obs_metrics.registry()
        labels = {"shape": f"{s}x{sk}x{d}.{dtype}"}
        if rule:
            labels["mask"] = rule
        if rep > 1:
            labels["rep"] = str(rep)
        if bshd:
            labels["layout"] = "bshd"
        if bwd:
            reg.gauge("flash.bwd_block_q", block_q, labels)
            reg.gauge("flash.bwd_block_k", block_k, labels)
            reg.gauge("flash.bwd_pairs_computed_pct", pct, labels)
        else:
            reg.gauge("flash.block_q", block_q, labels)
            reg.gauge("flash.block_k", block_k, labels)
            reg.gauge("flash.pairs_computed_pct", pct, labels)


def backward_vmem_bytes(block_q: int, block_k: int, s: int, d: int,
                        itemsize: int) -> int:
    """VMEM one grid step of the backward holds, reckoned from the shapes:
    the double-buffered q, do, k, v blocks, the dk, dv blocks and the whole
    sequence's dq block going out, the log-sum-exp and delta rows (eight
    sublanes each); the float32 accumulators (dq's for the whole sequence);
    and the tile's four float32 arrays (scores, probabilities, dp, ds)
    with the copies of p and ds, and of ds turned, in the operands' type."""
    dl = -(-d // _LANES) * _LANES
    blocks = 2 * ((2 * block_q + 4 * block_k + s) * dl * itemsize
                  + 2 * 8 * block_q * 4)
    scratch = (2 * block_k + s) * dl * 4
    scores = block_q * block_k * (4 * 4 + 3 * itemsize)
    return blocks + scratch + scores


def backward_tiles(s: int, sk: int, d: int, itemsize: int, mask=None):
    """The backward's (block_q, block_k): the largest pair of
    ``BACKWARD_TILES`` (``_largest_tiles``) that keeps the backward's own
    reckoning, ``backward_vmem_bytes`` (dq's scratch is of the whole
    sequence ``s``), within ``VMEM_BUDGET``; under a ``BlockDiffusionMask``
    a tile divides its half."""
    whole = s
    if isinstance(mask, WindowMask):
        return mask.tiles(s)
    if mask is not None:
        s = sk = mask.half
    return _largest_tiles(
        BACKWARD_TILES, s, sk, lambda bq, bk: backward_vmem_bytes(
            bq, bk, whole, d, itemsize) <= VMEM_BUDGET)


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      scale: float, causal: bool, block_q: int, block_k: int,
                      n_q: int, n_k: int, mask=None, sel=None,
                      q_tiles: Optional[int] = None, kinds=None,
                      sub: int = 0):
    """One (bh, k_block, q_block) grid step of the backward.  The tile is
    held keys by queries (``s^T = k q^T``): the log-sum-exp and delta of the
    query rows are then lane rows that broadcast down the sublanes, and
    dv, dk are plain products.  dk, dv accumulate over the query axis (the
    innermost), dq over both in a scratch for the whole sequence.  ``n_q``
    is the query axis' steps: the query tiles, or under a ``WindowMask``
    the tiles a band can touch (of ``q_tiles`` in all), the step's query
    tile then being ``mask.query_tile``'s.  ``kinds`` and ``sub`` as the
    forward's: a crossed tile is walked key row block by key row block."""
    ki, step = pl.program_id(1), pl.program_id(2)
    windowed = isinstance(mask, WindowMask)
    qi = mask.query_tile(ki, step, block_q, block_k) if windowed else step

    @pl.when((ki == 0) & (step == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(step == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile(masked):
        # ``masked`` as the forward's.  Operands as stored, float32
        # accumulation: the forward's rule
        q, do, k, v = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        nt = (((1,), (1,)), ((), ()))
        st = jax.lax.dot_general(k, q, nt,
                                 preferred_element_type=jnp.float32) * scale
        if sel is not None:
            # the keys' bitmap block: rows are keys, bits queries
            seen = unpack_tile(sel[1][0, 0], qi * block_q, block_q) != 0
            if masked:
                seen = seen & _at_or_before(st.shape, 1,
                                            ki * block_k - qi * block_q)
            st = jnp.where(seen, st, NEG_INF)
        elif windowed:
            if masked:
                st = jnp.where(_in_band(st.shape, 1, ki * block_k
                                        - qi * block_q, mask.window), st,
                               NEG_INF)
        elif mask is not None:
            if masked is not None:
                st = jnp.where(_band_mask(mask, masked, 1, st.shape), st,
                               NEG_INF)
        elif masked:
            # q_pos >= k_pos, the tile's offsets moved to the scalar side
            col_less_row = lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1) - lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            st = jnp.where(col_less_row >= ki * block_k - qi * block_q,
                           st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                 # (BK, BQ)
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, nt,
                                  preferred_element_type=jnp.float32)
        # scale is applied to the sums, once a row and not once a score
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[rows, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _walk(edge):
        # a crossed tile of the kind ``edge``, as the forward's walk with
        # the sides exchanged: for each row block of keys the query
        # sub-blocks that hold an allowed pair.  What ``_tile`` computes,
        # one kind of product or of arithmetic after another over all the
        # row blocks: in that order the kernel took a tenth to a quarter
        # less than row block after row block (PERF.md section 6, PR 42)
        nt, tn = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))
        dot = functools.partial(jax.lax.dot_general,
                                preferred_element_type=jnp.float32)
        blocks, sts = [], []
        for c, (lo, hi, fates) in enumerate(
                edge.spans(block_k, sub, by_key=True)):
            if lo == hi:
                continue
            keys, qs = slice(c * sub, (c + 1) * sub), \
                slice(lo * sub, hi * sub)
            st = dot(k_ref[0, keys], q_ref[0, qs], nt) * scale
            st = edge.mask_span(st, lo * sub, c * sub, sub, fates, 1)
            if sel is not None:
                seen = unpack_tile(sel[1][0, 0, keys],
                                   qi * block_q + lo * sub, (hi - lo) * sub)
                st = jnp.where(seen != 0, st, NEG_INF)
            blocks.append((keys, qs))
            sts.append(st)
        dpts = [dot(v_ref[0, keys], do_ref[0, qs], nt) for keys, qs in blocks]
        pts = [jnp.exp(st - lse_ref[0, :, qs])
               for (_, qs), st in zip(blocks, sts)]
        dsts = [(pt * (dpt - delta_ref[0, :, qs])).astype(q_ref.dtype)
                for (_, qs), pt, dpt in zip(blocks, pts, dpts)]
        dvs = [dot(pt.astype(do_ref.dtype), do_ref[0, qs], tn)
               for (_, qs), pt in zip(blocks, pts)]
        dks = [dot(dst, q_ref[0, qs], tn)
               for (_, qs), dst in zip(blocks, dsts)]
        for (keys, _), dv, dk in zip(blocks, dvs, dks):
            dv_acc[keys] += dv
            dk_acc[keys] += dk
        for (keys, qs), dst in zip(blocks, dsts):
            rows = pl.ds(pl.multiple_of(qi * block_q + qs.start, sub),
                         qs.stop - qs.start)
            dq_acc[rows, :] += dot(dst, k_ref[0, keys],
                                   (((0,), (0,)), ((), ())))

    _run = functools.partial(_run_tile, kinds, _tile, _walk, qi, ki)

    if sel is not None:
        _when_selected(_run, sel[0], causal, qi, ki, block_q, block_k)
    elif windowed:
        _when_window(_run, mask, qi < q_tiles, qi, ki, block_q, block_k)
    elif mask is not None:
        _when_block_diffusion(_run, mask, qi, ki, block_q, block_k)
    elif causal:
        _when_causal(_run, qi, ki, block_q, block_k)     # as the forward
    else:
        _tile(False)

    @pl.when(step == n_q - 1)
    def _finish_dkv():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((ki == n_k - 1) & (step == n_q - 1))
    def _finish_dq():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_kernel_sel(fate_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref,
                          v_ref, words_ref, *rest, heads: int, **kw):
    """``_flash_bwd_kernel`` under a ``SelectedKeysMask``: the fate table
    (key tiles by query tiles) first, the keys' bitmap block after v."""
    fate = fate_ref[pl.program_id(0) // heads, pl.program_id(1),
                    pl.program_id(2)]
    _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *rest,
                      sel=(fate, words_ref), **kw)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "mask", "sub",
    "heads"))
def _flash_bwd_pallas(q3, k3, v3, o3, lse, do3, *, scale, causal, interpret,
                      block_q=None, block_k=None, mask=None, selection=None,
                      sub=None, heads=None):
    """The flash backward from the saved log-sum-exp: (dq, dk, dv) for
    (BH, S, D) q and do, (BH, SK, D) k and v, in one Pallas call named
    ``flash_bwd`` (``flash_bwd_bd``, ``flash_bwd_sel``, ``flash_win_bwd``
    under a mask rule).
    Its tiles come from the shapes (``backward_tiles``); ``sub`` as the
    forward's.  With (B * KV, SK, D) k and v (the forward's ``rep``) dk and
    dv still come out one a query row, (BH, SK, D): their sum over a group
    is the caller's (``_sum_groups``).  ``heads = H``: q, out, do (B, S, H *
    D) and k, v (B, SK, KV * D) as the forward's, dq (B, S, H * D) and dk,
    dv (B, SK, H * D) back; ``lse`` stays (B * H, S).

    Jitted for the reason ``_flash_fwd_pallas`` is: one trace of the
    kernel's body for all of a model's layers."""
    bh, s, d, rep = _rows_of(q3, k3, heads)
    sk = k3.shape[1]
    selected = isinstance(mask, SelectedKeysMask)
    if block_q is None or block_k is None:
        tq, tk = backward_tiles(s, sk, d, q3.dtype.itemsize,
                                None if selected else mask)
        block_q, block_k = block_q or tq, block_k or tk
    sub = SUB_BLOCK if sub is None else sub
    _note_tiles((s, sk, d, q3.dtype.name), block_q, block_k, bwd=True,
                mask=mask, causal=causal, sub=sub, rep=rep,
                bshd=heads is not None)
    windowed = isinstance(mask, WindowMask)
    q_tiles, n_k = s // block_q, sk // block_k
    # the query axis' steps: under a band, the tiles it can touch
    n_q = mask.query_steps(s, block_q, block_k) if windowed else q_tiles
    # delta = rowsum(do * out), float32, in XLA: one pass over two arrays
    # the step already holds; a row vector per head, as the log-sum-exp
    delta = do3.astype(jnp.float32) * o3.astype(jnp.float32)
    if heads is None:
        delta = delta.sum(-1)
    else:
        # summed over each head's own columns where they lie, and the (B,
        # S / 8, H, 8) sums (not the operands) turned to the rows the kernel
        # reads
        delta = by_head(delta, heads).sum(-1).transpose(0, 2, 1, 3)
    kern = functools.partial(
        _flash_bwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_q=n_q, n_k=n_k, mask=mask, sub=sub,
        kinds=crossed_kinds(mask, causal, s, sk, block_q, block_k, sub),
        **({"q_tiles": q_tiles} if windowed else {}))
    if windowed:
        # a step past the band names the band's last tile again
        first = lambda ki, step: jnp.minimum(
            mask.query_tile(ki, step, block_q, block_k),
            mask.query_span(ki, block_q, block_k, q_tiles)[1])
    elif mask is not None and not selected:
        first = lambda ki, qi: mask.query_tile(ki, qi, block_q, block_k)
    elif causal:
        # a skipped step names the first query block its key tile needs:
        # the block the first step that runs will want, fetched once
        first = lambda ki, qi: jnp.minimum(
            jnp.maximum(qi, ki * block_k // block_q), n_q - 1)
    else:
        first = lambda ki, qi: qi
    # the index maps take the grid's indices and, under a selection, the
    # scalar-prefetch table after them
    q_at, kv_at = _head_at(heads), _head_at(heads, rep)

    def of_head(at, tile):
        # the index map of an operand's block: its head by ``at``, its tile
        # by ``tile(ki, qi)``
        def index(b, ki, qi, *_):
            row, col = at(b)
            return row, tile(ki, qi), col
        return index

    q_spec = pl.BlockSpec((1, block_q, d), of_head(q_at, first))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b, ki, qi, *_: (b, 0, first(ki, qi)))
    # k and v come from the group's key-value row (the forward's kv_map);
    # dk and dv go out one a query row
    k_spec = pl.BlockSpec((1, block_k, d), of_head(kv_at, lambda ki, qi: ki))
    dk_spec = pl.BlockSpec((1, block_k, d), of_head(q_at, lambda ki, qi: ki))
    in_specs = [q_spec, q_spec, row_spec, row_spec, k_spec, k_spec]
    out_specs = [pl.BlockSpec((1, s, d), of_head(q_at, lambda ki, qi: 0)),
                 dk_spec, dk_spec]
    scratch_shapes = [
        pltpu.VMEM((s, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    operands = (q3, do3, lse.reshape(bh, 1, s), delta.reshape(bh, 1, s), k3,
                v3)
    # dk, dv: k's shape with the queries' heads
    wide = (bh, sk, d) if heads is None else (q3.shape[0], sk, q3.shape[2])
    if selected:
        # the keys' bitmap block of the query tile's group
        sel_heads = bh // selection.by_key.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k, _LANES), lambda b, ki, qi, *_: (
                b // sel_heads, first(ki, qi) * block_q // SEL_GROUP, ki, 0)))
        kern = functools.partial(_flash_bwd_kernel_sel, heads=sel_heads,
                                 **kern.keywords)
        operands = (SelectedKeysMask.tile_fates(
            selection.blocks, block_q, block_k, by_key=True),) + operands \
            + (selection.by_key,)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, n_k, n_q), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes))
    else:
        grid = dict(grid=(bh, n_k, n_q), in_specs=in_specs,
                    out_specs=out_specs, scratch_shapes=scratch_shapes)
    dq, dk, dv = pl.pallas_call(
        kern,
        name="flash_bwd" if mask is None else _kernel_name(mask, "bwd"),
        **grid,
        out_shape=[
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct(wide, k3.dtype),
            jax.ShapeDtypeStruct(wide, v3.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * VMEM_BUDGET),
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q3, k3, v3, scale, causal, block_q, block_k, interpret, mask,
           heads):
    out, _ = _flash_fwd_pallas(q3, k3, v3, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, mask=mask, heads=heads)
    return out


def _flash_fwd_rule(q3, k3, v3, scale, causal, block_q, block_k, interpret,
                    mask, heads):
    out, lse = _flash_fwd_pallas(q3, k3, v3, scale=scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret, mask=mask, heads=heads)
    # names a block's remat policy can keep (models/routed_lm.py SAVED):
    # on the values themselves, before they go into the result and the
    # residuals, so that a policy which saves them needs no second call.
    # q3, k3, v3 carry none: they are rebuilt from the projections
    # Under a band the two have names of their own: a windowed forward
    # costs a fraction of a causal one, so a policy may keep the one kind's
    # output and compute the other's again
    win = "win_" if isinstance(mask, WindowMask) else ""
    out = checkpoint_name(out, f"flash_{win}out")
    lse = checkpoint_name(lse, f"flash_{win}lse")
    return out, (q3, k3, v3, out, lse)


def _sum_groups(dx3, like, heads=None):
    """dk or dv as the backward kernel leaves it, one a query row (B * H, SK,
    D), summed over each key-value head's group to ``like``'s (B * KV, SK,
    D), in float32 (what the reduction that transposed ``jnp.repeat`` did).
    With ``heads = H``: (B, SK, H * D) summed over ``rep`` on its by-head
    view, (B, SK / 8, KV, rep, 8, D), to ``like``'s (B, SK, KV * D).
    Without groups ``dx3`` itself."""
    if dx3.shape == like.shape:
        return dx3
    if heads is None:
        return dx3.reshape(like.shape[0], -1, *dx3.shape[1:]).sum(
            axis=1, dtype=jnp.float32).astype(dx3.dtype)
    dx5 = by_head(dx3, heads)
    b, s8, _, _, d = dx5.shape
    rep = dx3.shape[2] // like.shape[2]
    return from_heads(dx5.reshape(b, s8, heads // rep, rep, 8, d).sum(
        axis=3, dtype=jnp.float32).astype(dx3.dtype))


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, mask, heads,
                    res, do3):
    # the forward's tiles stop here: the backward derives its own from the
    # shapes (backward_tiles), whatever the forward was given
    q3, k3, v3, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(q3, k3, v3, out, lse, do3, scale=scale,
                                   causal=causal, interpret=interpret,
                                   mask=mask, heads=heads)
    return dq, _sum_groups(dk, k3, heads), _sum_groups(dv, v3, heads)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# under a SelectedKeysMask: a function of its own (the selection is an
# operand and the log-sum-exp a result), so that without the rule ``_flash``
# and what it lowers to are what they were
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_sel(q3, k3, v3, selection, scale, causal, block_q, block_k,
               interpret, heads):
    return _flash_fwd_pallas(
        q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, mask=SelectedKeysMask(),
        selection=selection, heads=heads)


def _flash_sel_fwd_rule(q3, k3, v3, selection, scale, causal, block_q,
                        block_k, interpret, heads):
    out, lse = _flash_sel(q3, k3, v3, selection, scale, causal, block_q,
                          block_k, interpret, heads)
    out = checkpoint_name(out, "flash_out")         # as _flash_fwd_rule
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q3, k3, v3, selection, out, lse)


def _flash_sel_bwd_rule(scale, causal, block_q, block_k, interpret, heads,
                        res, cts):
    # the log-sum-exp's cotangent is dropped: what reads it (the indexer's
    # KL term) takes it as a constant
    q3, k3, v3, selection, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        q3, k3, v3, out, lse, cts[0], scale=scale, causal=causal,
        interpret=interpret, mask=SelectedKeysMask(), selection=selection,
        heads=heads)
    return dq, _sum_groups(dk, k3, heads), _sum_groups(dv, v3, heads), None


_flash_sel.defvjp(_flash_sel_fwd_rule, _flash_sel_bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    mask=None, selection: Optional[Selection] = None,
                    return_lse: bool = False, heads: Optional[int] = None):
    """Fused attention, (B, S, H, D) layout (``full_attention`` oracle).

    ``k`` and ``v`` are (B, SK, KV, D) with ``KV`` a divisor of ``H``
    (grouped-query attention): key-value head ``j`` serves the query heads
    ``j * rep .. (j + 1) * rep - 1``, ``rep = H // KV``, as
    ``jnp.repeat(k, rep, axis=2)`` would lay them out, but nothing is
    spread: both kernels' index maps fetch a grid step's key and value
    block from head ``h // rep``, and the backward's dk, dv, one a query
    head out of the kernel, are summed over each group in float32.  ``KV ==
    H`` is plain multi-head attention, the program it always was.

    The operands' rank decides the kernels' operand layout.  Rank 4, as
    above: every operand is turned to ``(B * H, S, D)`` and the result
    turned back, as it always was.  Rank 3: q ``(B, S, H * D)`` and k, v
    ``(B, SK, KV * D)`` with ``heads = H``, the arrays as the projections
    leave them, where a head is whole lane tiles (``lane_tiled``: ``D %
    128 == 0``, ``S % 8 == 0``).  The kernels then read each head's ``D``
    columns where they lie, the result comes back ``(B, S, H * D)`` for
    ``o_proj`` to read as it is, the cotangent takes the same way, and
    nothing is turned.  That pays only for a caller whose own work on q, k
    and the result stays on those arrays (``by_head``): on this chip a ``(B,
    S, H, D)`` view of them is other bytes, a pass over the array (PERF.md
    section 6, PR 45), which is why four-axis operands keep their path.

    Sequence lengths must be multiples of ``DEFAULT_BLOCK`` (pad upstream;
    ``TransformerLM`` does).  ``block_q`` / ``block_k`` override the
    forward's tiles, which are otherwise derived from the shapes
    (``forward_tiles``); they must be multiples of 128 that divide the
    lengths.  Differentiable via the Pallas flash backward
    (``_flash_bwd_pallas``), whose tiles are derived from the shapes
    (``backward_tiles``) whatever the forward's are.

    ``mask`` is a rule beside ``causal`` (and instead of it): a
    ``BlockDiffusionMask(half, block)`` over ``2 half`` positions, queries
    and keys alike, each half a multiple of ``DEFAULT_BLOCK``.  Both kernels
    prune, mask and fetch tile by tile by the rule.  Or a
    ``SelectedKeysMask()`` with its ``selection`` (a ``Selection`` over
    ``s`` queries and as many keys), beside ``causal`` or without it;
    ``return_lse`` then also returns the float32 log-sum-exp (B, H, S), a
    constant to whatever reads it.  Or a ``WindowMask(window)`` over ``s``
    positions, queries and keys alike (it implies ``causal``, which may be
    passed or not): both kernels' grids then span only the tiles the band
    can touch.
    """
    if interpret is None:
        interpret = _default_interpret()
    if q.ndim == 3:
        b, s, width = q.shape
        if not heads or width % heads \
                or not lane_tiled(width // heads, s, k.shape[1]) \
                or k.shape[2] % (width // heads):
            raise ValueError(
                f"(B, S, H * D) operands come with heads=H, D whole tiles of "
                f"{_LANES} lanes and the lengths multiples of 8: got q "
                f"{q.shape}, k {k.shape}, heads={heads}")
        h, d = heads, width // heads
        sk, kv = k.shape[1], k.shape[2] // d
    elif heads is not None:
        raise ValueError(f"heads={heads} goes with (B, S, H * D) operands, "
                         f"not with q {q.shape}")
    else:
        b, s, h, d = q.shape
        sk, kv = k.shape[1], k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if h % kv or v.shape != k.shape:
        raise ValueError(f"{kv} key-value heads do not divide {h} query "
                         f"heads, or k {k.shape} and v {v.shape} differ")
    selected = isinstance(mask, SelectedKeysMask)
    if selected != (selection is not None) or (return_lse and not selected):
        raise ValueError("a SelectedKeysMask and its selection go together, "
                         "and only they return the log-sum-exp")
    if selected:
        want = (b, -(-s // SEL_GROUP), s, _LANES)
        if s != sk or selection.by_query.shape != want \
                or selection.by_key.shape != want:
            raise ValueError(f"a selection over ({s}, {sk}) positions is two "
                             f"bitmaps of {want}, not "
                             f"{selection.by_query.shape}")
    elif isinstance(mask, WindowMask):
        if s != sk:
            raise ValueError(f"{mask} is a rule over queries and keys "
                             f"alike: got ({s}, {sk})")
    elif mask is not None and (causal or s != sk or s != 2 * mask.half):
        raise ValueError(f"{mask} is a rule over {2 * mask.half} positions, "
                         f"queries and keys alike, and not beside causal: "
                         f"got ({s}, {sk}), causal={causal}")
    half = isinstance(mask, BlockDiffusionMask)
    for n, block in ((mask.half if half else s, block_q),
                     (mask.half if half else sk, block_k)):
        block = DEFAULT_BLOCK if block is None else block
        if n % block or block % _LANES:
            raise ValueError(f"seq lengths ({s}, {sk}) must be multiples "
                             f"of blocks ({block_q}, {block_k}), and those "
                             f"of {_LANES}")
    if heads is not None:
        # the projections' arrays: the kernels read each head where it lies
        to3 = back = lambda x: x
    else:
        # heads after the batch: row b * H + h of q, row b * KV + h // rep
        # of k, v
        to3 = lambda x: jnp.moveaxis(x, 2, 1).reshape(-1, x.shape[1], d)
        back = lambda out3: jnp.moveaxis(out3.reshape(b, h, s, d), 1, 2)
    if selected:
        out3, lse = _flash_sel(to3(q), to3(k), to3(v), selection, scale,
                               causal, block_q, block_k, interpret, heads)
    else:
        out3 = _flash(to3(q), to3(k), to3(v), scale, causal, block_q,
                      block_k, interpret, mask, heads)
    out = back(out3)
    return (out, lse.reshape(b, h, s)) if return_lse else out
