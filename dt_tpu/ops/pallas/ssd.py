"""The Mamba-2 recurrence (``ops/ssm.py`` ``ssd_scan``) as Pallas TPU kernels
with a hand-written backward.

The reference's recurrent ceiling is the cuDNN fused RNN
(``src/operator/cudnn_rnn-inl.h:1``; SURVEY §5.7), one position at a time;
``ops/ssm.py`` has the recurrence that trains in parallel over the sequence
and its XLA body (``ssd_scan_xla``), which writes a ``chunk x chunk`` decay
tensor of every head and chunk to HBM (537 MB a layer at the hybrid cell's
shapes) and is differentiated as written, loops included.  Here a chunk's
squares live in VMEM only and no loop is differentiated.

**What a chunk computes**, one head (``x`` (q, P), ``B``, ``C`` (q, N), the
steps ``dt`` (q,), ``cs`` the running sum of ``a dt`` inside the chunk, ``v =
cs - log dt``, ``S`` the state entering it, held (N, P); operands of products
in ``x``'s type, sums and everything named ``cs``, ``v`` or ``S`` float32)::

    M    = tril(C B^T) * exp(cs_i - v_j)      (q, q): the decay times dt_j
    y    = M x + (exp(cs) C) S                the rows of C scaled
    S'   = exp(cs_end) S + (B^T exp(cs_end - v)) x     the columns of B^T scaled

The steps enter through ``exp(. + log dt)``, so ``x`` goes to the matrix unit
as it is stored and every scale lands on an operand whose index runs along
the lanes or is shared with ``cs_i``.  That is the design's one rule: **on
this chip the cross-lane unit sets the pace, not the matrix unit** (PERF.md
section 6, PR 40: the first version, written as the mathematics reads, spent
two thirds of its backward on broadcasts of a column along the lanes, a
fifth on turning the left operand of three products and a quarter on sums
along the lanes).  So a head costs the forward one column broadcast
(``cs_i``), the backward one (``v_j``) and one sum along the lanes, and no
product has a turned left operand: ``B`` and ``C`` come in both ways round
(turned by XLA, 2 MB a layer each), the state is held (N, P), and the
backward holds its squares sources by targets and returns ``dB`` and ``dC``
turned.

**The backward**, from ``dy`` and ``dS'`` (the gradient of the state leaving
the chunk; zero after the last), squares written sources ``j`` by targets
``i`` (``Mt[j, i] = M[i, j]``)::

    dMt   = x dy^T                      dx   = Mt dy + (B exp(cs_end - v)) dS'
    dCBt  = sum over the group's heads of dMt * exp(cs_i - v_j)
    Gt    = dMt * Mt
    dCt   = B^T dCBt + exp(cs) * (S dy^T)       dBt = C^T dCBt^T + exp(cs_end - v) * (dS' x^T)
    dS    = exp(cs_end) dS' + (C^T exp(cs)) dy
    tau   = exp(cs_end - v) * colsum(B^T * (dS' x^T))
    dcs_i = colsum(Gt)_i + colsum(C^T exp(cs) * (S dy^T))_i      (rows: free)
    dcs_j = -rowsum(Gt)_j - tau_j,   dlogdt_j = rowsum(Gt)_j + tau_j
    dcs_end += sum(tau) + exp(cs_end) sum(dS' * S)

These are the derivatives of the three lines above (what XLA derives from
``ssd_scan_xla``, with ``dt`` written as ``exp(log dt)``).  ``cs`` is a
cumulative sum and ``log dt`` a logarithm made by XLA outside the kernels
(2 MB a layer), so ``da`` and ``ddt`` are XLA's transposes of those: ``_ssd``
is the ``jax.custom_vjp``, over ``(x, B, C, cs, log dt)``.  A step of zero
(the padded tail) has ``log dt = -inf``, decays to nothing and gets no
gradient.

**Layouts.**  ``x``, ``y``, ``dy`` as ``(B, L, H P)`` (a free reshape), so
that a block's lane side is whole lane tiles; a lane tile holds ``128 / P``
heads (a *pack*: two heads at ``P`` = 64).  A head's products are made a lane
tile wide, against the pack's ``x``: that costs the matrix unit what the
head's own 64 lanes would, no lane is shuffled, and each head keeps its own
lanes of the result (``_pick``).  ``cs`` and ``v`` come positions-by-heads
(a head's column) and heads-by-positions (a head's row, which broadcasts down
the sublanes for nothing).

**Grid** ``(B, chunks, H / hb)``: ``hb`` heads a step, the head axis
innermost.  The states of *all* heads stay in a float32 VMEM scratch over the
sequential chunk axis (2 MB at 64 heads of 64 x 128); ``tril(C B^T)`` is made
at a group's first head block and kept in scratch for its others; the
backward walks the chunks from the last to the first carrying ``dS`` the same
way, accumulates ``dCBt``, ``dBt`` and ``dCt`` over a group's head blocks in
scratch and writes them at its last, so the sum over a group's heads never
reaches HBM.  ``hb`` comes from the shape (``head_block``).  The forward
writes the state entering each chunk (``(B, chunks, N, H P)`` float32), the
one residual the backward needs beyond its inputs.

The kernels are named ``ssd_fwd`` and ``ssd_bwd`` in a device trace.  Off
the TPU they run in the Pallas interpreter.  ``head_block`` returns None for
shapes the kernels do not take (a chunk, a state width or a pack that is not
whole lane tiles: the toy sizes of most tests) and ``ops/ssm.py`` then runs
``ssd_scan_xla``.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dt_tpu.obs import metrics as obs_metrics
from dt_tpu.ops.pallas.attention import VMEM_BUDGET
from dt_tpu.ops.pallas.kernels import _default_interpret

logger = logging.getLogger("dt_tpu")

F32 = jnp.float32
_LANES = 128
# heads a grid step, largest first.  A step costs about 0.35 us whatever it
# computes, and the heads of a step are unrolled in the kernel's body: at the
# hybrid cell's shapes 16 heads a step took 6% and 4% off the two kernels' 8
# (PERF.md section 6, PR 40) for twice the body to compile
HEAD_BLOCKS = (8, 4, 2, 1)
# the cap on a decay's exponent: above the diagonal, where tril(C B^T) is zero,
# cs_i - cs_j + log dt_j is positive and may not overflow
_CAP = 60.0

_NN = (((1,), (0,)), ((), ()))     # a b
_NT = (((1,), (1,)), ((), ()))     # a b^T


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=F32)


def _pack(p: int):
    """(heads in a pack, its lanes) for heads of ``p`` channels: as many
    heads as fill a lane tile, or one head of whole lane tiles; None where
    ``p`` is neither a divisor nor a multiple of 128."""
    if _LANES % p == 0:
        return _LANES // p, _LANES
    return (1, p) if p % _LANES == 0 else None


def vmem_bytes(hb: int, heads: int, p: int, n: int, q: int,
               itemsize: int) -> int:
    """VMEM one grid step of the backward (the larger of the two) holds,
    reckoned from the shapes: the double-buffered blocks (``x``, ``dy`` and
    ``dx``; ``B``, ``B^T``, ``C^T``, ``dB^T``, ``dC^T``; the entering state;
    the two positions-by-heads arrays, lane-padded, and the four
    heads-by-positions ones), the scratch (``dS`` of all heads, two ``q x q``
    squares, two ``N x q`` sums) and the step's float32 temporaries (six
    squares and ten pack-wide arrays)."""
    w = _pack(p)[1]
    blocks = 2 * (3 * q * hb * p * itemsize + 5 * q * n * itemsize
                  + hb * p * n * 4 + 2 * q * _LANES * 4 + 4 * 8 * q * 4)
    scratch = heads * p * n * 4 + 2 * q * q * 4 + 2 * q * n * 4
    temporaries = 6 * q * q * 4 + 10 * q * w * 4
    return blocks + scratch + temporaries


def head_block(heads: int, groups: int, p: int, n: int, q: int,
               itemsize: int):
    """Heads a grid step takes, for ``heads`` heads of ``p`` channels in
    ``groups`` groups, states of ``n`` and chunks of ``q`` positions: the
    largest of ``HEAD_BLOCKS`` that divides a group's heads, is whole packs
    and keeps ``vmem_bytes`` within ``VMEM_BUDGET``.  None where the kernels
    do not take the shape: ``q`` or ``n`` not whole lane tiles, ``p`` no
    divisor or multiple of 128, no candidate.

    The shapes the choice was checked at (Mosaic for a described v5e,
    ``tests/test_ssd_compile_tpu.py``, and on the chip): 64 heads of 64 in
    one group, states of 128, chunks of 256, bfloat16 (PR 40: 8 heads a
    step, 8.7 MiB by ``vmem_bytes``; 16 a step took 6% and 4% off the two
    kernels for twice the body); one chip's share of a mixer of 128 heads
    in 8 groups, 16 heads and their one group, at chunks of 128 over 8,192
    positions (PR 47: 8 heads a step, two steps a group, 3.6 MiB; the
    scratch holds 16 heads' states, 0.5 MiB, where the first holds 2 MiB,
    and a chunk's squares are a quarter of the size, so a grid step's fixed
    cost is a larger share of it: 0.15 ms a forward call and 0.25 a
    backward call in the cell's step, PERF.md section 5, PR 47)."""
    if q % _LANES or n % _LANES or _pack(p) is None or heads % groups:
        return None
    hp = _pack(p)[0]
    for hb in HEAD_BLOCKS:
        if (heads // groups) % hb == 0 and hb % hp == 0 and vmem_bytes(
                hb, heads, p, n, q, itemsize) <= VMEM_BUDGET:
            return hb
    return None


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _own(values, t: int, hp: int, p: int):
    """``values`` on head ``t``'s lanes of the pack, zero on the others'."""
    if hp == 1:
        return values
    at = _iota(values.shape, 1)
    return jnp.where((at >= t * p) & (at < (t + 1) * p), values,
                     jnp.zeros_like(values))


def _pick(parts, p: int, shape=None):
    """An array (of ``shape``, or of the parts' own) that holds ``parts[t]``
    on lanes ``[t p, (t + 1) p)``: each head of a pack keeps its own lanes
    of a product made a pack wide."""
    out = parts[0] if shape is None else jnp.broadcast_to(parts[0], shape)
    for t in range(1, len(parts)):
        out = jnp.where(_iota(out.shape, 1) < t * p, out, parts[t])
    return out


def _tile(a, width: int):
    """``a`` (rows, 128) side by side ``width / 128`` times."""
    k = width // _LANES
    return a if k == 1 else jnp.concatenate([a] * k, axis=1)


def _fwd_kernel(x_ref, c_ref, bt_ref, csc_ref, vr_ref, y_ref, st_out_ref,
                st_scr, cb_scr, *, p: int, kb: int):
    """One (batch, chunk, head block) step of the forward."""
    ci, hi = pl.program_id(1), pl.program_id(2)
    q, dtype = x_ref.shape[0], x_ref.dtype
    n, hb = c_ref.shape[1], csc_ref.shape[1]
    hp, w = _pack(p)

    @pl.when(ci == 0)
    def _():
        st_scr[hi] = jnp.zeros(st_scr.shape[1:], F32)

    @pl.when(hi % kb == 0)
    def _():
        cb = _dot(c_ref[...], bt_ref[...], _NN)
        cb_scr[...] = jnp.where(_iota((q, q), 0) >= _iota((q, q), 1), cb, 0.0)

    st_out_ref[...] = st_scr[hi]
    cm, bt, cb = c_ref[...], bt_ref[...], cb_scr[...]
    csc = csc_ref[...]
    for k in range(hb // hp):
        lanes = slice(k * w, (k + 1) * w)
        x2 = x_ref[:, lanes]
        st2 = st_scr[hi, :, lanes]
        st2d = st2.astype(dtype)
        ys, adds, ends = [], [], []
        for h in range(k * hp, (k + 1) * hp):
            # the one broadcast of a column along the lanes a head costs
            cs_rep = jnp.broadcast_to(csc[:, h:h + 1], (q, _LANES))
            vr = vr_ref[h:h + 1, :]
            end = csc[q - 1:q, h:h + 1]
            # inside the chunk: tril(C B^T) exp(cs_i - cs_j) dt_j against x
            m = (cb * jnp.exp(jnp.minimum(_tile(cs_rep, q) - vr, _CAP))
                 ).astype(dtype)
            # what the entering state gives each position: (exp(cs) C) S^T
            ce = (cm * _tile(jnp.exp(cs_rep), n)).astype(dtype)
            ys.append(_dot(m, x2, _NN) + _dot(ce, st2d, _NN))
            # what the chunk adds to the state: (B^T exp(cs_end - cs) dt) x
            bs = (bt * jnp.exp(end - vr)).astype(dtype)
            adds.append(_dot(bs, x2, _NN))
            ends.append(jnp.exp(end))
        y_ref[:, lanes] = _pick(ys, p).astype(dtype)
        st_scr[hi, :, lanes] = _pick(ends, p, (1, w)) * st2 \
            + _pick(adds, p)


def _bwd_kernel(x_ref, dy_ref, b_ref, bt_ref, ct_ref, vc_ref, csr_ref, vr_ref,
                st_in_ref, dx_ref, dbt_ref, dct_ref, gcol_ref, dcsr_ref,
                dldr_ref, dst_scr, cbt_scr, dcbt_scr, dbt_scr, dct_scr, *,
                p: int, kb: int):
    """One (batch, chunk from the last, head block) step of the backward."""
    ci, hi = pl.program_id(1), pl.program_id(2)
    q, dtype = x_ref.shape[0], x_ref.dtype
    n, hb = b_ref.shape[1], vc_ref.shape[1]
    hp, w = _pack(p)
    # sources by targets: a source at or before its target
    upper = lambda: _iota((q, q), 0) <= _iota((q, q), 1)  # noqa: E731

    @pl.when(ci == 0)
    def _():
        dst_scr[hi] = jnp.zeros(dst_scr.shape[1:], F32)

    @pl.when(hi % kb == 0)
    def _():
        cbt_scr[...] = jnp.where(upper(), _dot(b_ref[...], ct_ref[...], _NN),
                                 0.0)
        dcbt_scr[...] = jnp.zeros_like(dcbt_scr)
        dbt_scr[...] = jnp.zeros_like(dbt_scr)
        dct_scr[...] = jnp.zeros_like(dct_scr)

    bm, bt, ct, cbt = b_ref[...], bt_ref[...], ct_ref[...], cbt_scr[...]
    bt32, ct32 = bt.astype(F32), ct.astype(F32)
    vc = vc_ref[...]
    last = _iota((1, q), 1) == q - 1
    gcols = []
    for k in range(hb // hp):
        lanes = slice(k * w, (k + 1) * w)
        x2, dy2 = x_ref[:, lanes], dy_ref[:, lanes]
        st2 = st_in_ref[:, lanes]
        st2d = st2.astype(dtype)
        dsn2 = dst_scr[hi, :, lanes]
        dsn2d = dsn2.astype(dtype)
        state_products = dsn2 * st2
        dxs, dsts, ends = [], [], []
        for t, h in enumerate(range(k * hp, (k + 1) * hp)):
            v_rep = jnp.broadcast_to(vc[:, h:h + 1], (q, _LANES))
            csr, vr = csr_ref[h:h + 1, :], vr_ref[h:h + 1, :]
            # cs at the chunk's end, by a sum: a lane picked out of a row
            # does not broadcast down the sublanes
            end = jnp.sum(jnp.where(last, csr, 0.0), axis=1, keepdims=True)
            # the square, held sources by targets: no product is turned
            pt = jnp.exp(jnp.minimum(csr - _tile(v_rep, q), _CAP))
            mt32 = cbt * pt
            dmt = _dot(_own(x2, t, hp, p), dy2, _NT)
            bsn = (bm * _tile(jnp.exp(end - v_rep), n)).astype(dtype)
            dxs.append(_dot(mt32.astype(dtype), dy2, _NN)
                       + _dot(bsn, dsn2d, _NN))
            dcbt_scr[...] += dmt * pt
            g = dmt * mt32
            gcols.append(jnp.sum(g, axis=1, keepdims=True))
            # the entering state's part of y
            e_row = jnp.exp(csr)
            cet32 = ct32 * e_row
            dsts.append(_dot(cet32.astype(dtype), dy2, _NN))
            dcet = _dot(_own(st2d, t, hp, p), dy2, _NT)
            dct_scr[...] += dcet * e_row
            # the state's path
            s_row = jnp.exp(end - vr)
            dbs = _dot(_own(dsn2d, t, hp, p), x2, _NT)
            dbt_scr[...] += dbs * s_row
            tau = jnp.sum(dbs * bt32, axis=0, keepdims=True) * s_row
            ends.append(jnp.exp(end))
            to_end = jnp.sum(tau, keepdims=True) + ends[-1] * jnp.sum(
                _own(state_products, t, hp, p), keepdims=True)
            dcsr_ref[h:h + 1, :] = jnp.sum(g, axis=0, keepdims=True) \
                + jnp.sum(cet32 * dcet, axis=0, keepdims=True) - tau \
                + jnp.where(last, to_end, 0.0)
            dldr_ref[h:h + 1, :] = tau
        dx_ref[:, lanes] = _pick(dxs, p).astype(dtype)
        dst_scr[hi, :, lanes] = _pick(ends, p, (1, w)) * dsn2 \
            + _pick(dsts, p)
    gcol_ref[...] = _pick(gcols, 1, (q, hb))

    @pl.when(hi % kb == kb - 1)
    def _():
        dcbt = jnp.where(upper(), dcbt_scr[...], 0.0).astype(dtype)
        dct_ref[...] = (dct_scr[...] + _dot(bt, dcbt, _NN)
                        ).astype(dct_ref.dtype)
        dbt_ref[...] = (dbt_scr[...] + _dot(ct, dcbt, _NT)
                        ).astype(dbt_ref.dtype)


def _specs(q, hb, p, n, kb, chunk_of):
    """The block specs the two kernels share: ``x``-like; ``B``-like and its
    turn; the positions-by-heads and heads-by-positions arrays; a chunk's
    state.  ``chunk_of`` turns the grid's chunk index into the chunk's."""
    return dict(
        x=pl.BlockSpec((None, q, hb * p),
                       lambda b, ci, hi: (b, chunk_of(ci), hi)),
        bc=pl.BlockSpec((None, q, n),
                        lambda b, ci, hi: (b, chunk_of(ci), hi // kb)),
        bct=pl.BlockSpec((None, n, q),
                         lambda b, ci, hi: (b, hi // kb, chunk_of(ci))),
        col=pl.BlockSpec((None, None, q, hb),
                         lambda b, ci, hi: (b, hi, chunk_of(ci), 0)),
        row=pl.BlockSpec((None, None, hb, q),
                         lambda b, ci, hi: (b, hi, 0, chunk_of(ci))),
        state=pl.BlockSpec((None, None, n, hb * p),
                           lambda b, ci, hi: (b, chunk_of(ci), 0, hi)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=2 * VMEM_BUDGET)


def _dims(x2, cm, col, q, n):
    """(batch, chunks, heads, a head's channels, head blocks, heads a block,
    groups, head blocks a group) of a call, from its operands."""
    bsz, lp, width = x2.shape
    hblocks, hb = col.shape[1], col.shape[3]
    heads, groups = hblocks * hb, cm.shape[2] // n
    return (bsz, lp // q, heads, width // heads, hblocks, hb, groups,
            hblocks // groups)


@functools.partial(jax.jit, inline=True, static_argnames=("q", "n",
                                                          "interpret"))
def _ssd_fwd_pallas(x2, cm, bt, csc, vr, *, q, n, interpret):
    """``x2`` (B, L, H P), ``cm`` (B, L, G N), ``bt`` (B, G N, L), ``csc``
    (B, H / hb, L, hb) float32, ``vr`` (B, H / hb, hb, L) float32 -> (``y2``
    (B, L, H P), the entering states (B, L / q, N, H P) float32).  Jitted and
    inlined as the flash kernels are: a model's layers share one trace of the
    body, and the call keeps its caller's scope."""
    bsz, nc, heads, p, hblocks, hb, groups, kb = _dims(x2, cm, csc, q, n)
    sp = _specs(q, hb, p, n, kb, lambda ci: ci)
    flops = 2 * bsz * nc * (groups * q * q * n
                            + heads * (q * q * p + 2 * q * p * n))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, kb=kb),
        name="ssd_fwd",
        grid=(bsz, nc, hblocks),
        in_specs=[sp["x"], sp["bc"], sp["bct"], sp["col"], sp["row"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, n, heads * p), F32)],
        scratch_shapes=[pltpu.VMEM((hblocks, n, hb * p), F32),
                        pltpu.VMEM((q, q), F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=bsz * nc * heads * q * q,
            bytes_accessed=2 * x2.size * x2.dtype.itemsize
            + 2 * cm.size * cm.dtype.itemsize + 2 * csc.size * 4
            + bsz * nc * heads * p * n * 4),
        interpret=interpret,
    )(x2, cm, bt, csc, vr)


@functools.partial(jax.jit, inline=True, static_argnames=("q", "n",
                                                          "interpret"))
def _ssd_bwd_pallas(x2, dy2, bm, bt, ct, vc, csr, vr, states, *, q, n,
                    interpret):
    """The backward from ``dy2`` and the entering states the forward saved,
    in one Pallas call named ``ssd_bwd``: (dx2; the gradients of ``B`` and
    ``C`` turned, (B, G N, L); the sums of G over the targets, positions by
    heads; the gradients of ``cs`` and of ``log dt`` but for those sums,
    heads by positions)."""
    bsz, nc, heads, p, hblocks, hb, groups, kb = _dims(x2, bm, vc, q, n)
    sp = _specs(q, hb, p, n, kb, lambda ci: nc - 1 - ci)
    flops = 2 * bsz * nc * (3 * groups * q * q * n
                            + heads * (2 * q * q * p + 4 * q * p * n))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, kb=kb),
        name="ssd_bwd",
        grid=(bsz, nc, hblocks),
        in_specs=[sp["x"], sp["x"], sp["bc"], sp["bct"], sp["bct"],
                  sp["col"], sp["row"], sp["row"], sp["state"]],
        out_specs=[sp["x"], sp["bct"], sp["bct"], sp["col"], sp["row"],
                   sp["row"]],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct(bt.shape, bt.dtype),
                   jax.ShapeDtypeStruct(ct.shape, ct.dtype),
                   jax.ShapeDtypeStruct(vc.shape, F32),
                   jax.ShapeDtypeStruct(csr.shape, F32),
                   jax.ShapeDtypeStruct(csr.shape, F32)],
        scratch_shapes=[pltpu.VMEM((hblocks, n, hb * p), F32),
                        pltpu.VMEM((q, q), F32), pltpu.VMEM((q, q), F32),
                        pltpu.VMEM((n, q), F32), pltpu.VMEM((n, q), F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=bsz * nc * heads * q * q,
            bytes_accessed=3 * x2.size * x2.dtype.itemsize
            + 5 * bm.size * bm.dtype.itemsize + 6 * vc.size * 4
            + states.size * 4),
        interpret=interpret,
    )(x2, dy2, bm, bt, ct, vc, csr, vr, states)


def _layouts(cs, ldt, hb):
    """``cs`` and ``log dt`` (B, L, H) -> ``cs`` and ``v = cs - log dt``, each
    positions by heads (B, H / hb, L, hb) and heads by positions (B, H / hb,
    hb, L)."""
    bsz, lp, h = cs.shape
    col = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(bsz, lp, h // hb, hb), 2, 1)
    csc, vc = col(cs), col(cs - ldt)
    return csc, jnp.swapaxes(csc, 2, 3), vc, jnp.swapaxes(vc, 2, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ssd(x2, bm, cm, cs, ldt, hb, q, n, interpret):
    return _ssd_fwd_rule(x2, bm, cm, cs, ldt, hb, q, n, interpret)[0]


def _ssd_fwd_rule(x2, bm, cm, cs, ldt, hb, q, n, interpret):
    csc, csr, vc, vr = _layouts(cs, ldt, hb)
    bt = jnp.swapaxes(bm, 1, 2)
    y2, states = _ssd_fwd_pallas(x2, cm, bt, csc, vr, q=q, n=n,
                                 interpret=interpret)
    return y2, (x2, bm, bt, jnp.swapaxes(cm, 1, 2), vc, csr, vr, states)


def _ssd_bwd_rule(hb, q, n, interpret, residuals, dy2):
    x2, bm, bt, ct, vc, csr, vr, states = residuals
    dx2, dbt, dct, gcol, dcsr, dldr = _ssd_bwd_pallas(
        x2, dy2, bm, bt, ct, vc, csr, vr, states, q=q, n=n,
        interpret=interpret)
    bsz, lp, _ = x2.shape
    flat = lambda t: t.reshape(bsz, lp, -1)  # noqa: E731
    by_row = lambda t: flat(jnp.moveaxis(jnp.swapaxes(t, 2, 3), 1, 2))  # noqa: E731
    gcol = flat(jnp.moveaxis(gcol, 1, 2))
    return (dx2, jnp.swapaxes(dbt, 1, 2), jnp.swapaxes(dct, 1, 2),
            by_row(dcsr) - gcol, by_row(dldr) + gcol)


_ssd.defvjp(_ssd_fwd_rule, _ssd_bwd_rule)

# calls traced so far in this process: [through the kernels, through XLA]
_calls = [0, 0]


def note_path(shape, hb) -> None:
    """Record at trace time which path a call of ``ssd_scan`` took (``hb``
    None: ``ssd_scan_xla``): a ``# ssd_scan`` debug line, and with the
    metrics plane on the gauges ``ssd.kernel_calls`` and ``ssd.xla_calls``,
    the process's counts so far."""
    _calls[hb is None] += 1
    logger.debug("# ssd_scan b=%d l=%d h=%d p=%d g=%d n=%d q=%d dtype=%s "
                 "hb=%s", *shape, hb)
    if obs_metrics.enabled():
        reg = obs_metrics.registry()
        reg.gauge("ssd.kernel_calls", _calls[0])
        reg.gauge("ssd.xla_calls", _calls[1])


def ssd_scan_pallas(x, dt, a, b, c, *, q: int, hb: int, interpret=None):
    """``ops/ssm.py`` ``ssd_scan`` through the kernels, for a shape
    ``head_block`` gave ``hb`` for and chunks of ``q`` positions."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = (-l) % q
    if pad:   # dt = 0 neither decays nor feeds the state
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    lp, dtype = l + pad, x.dtype
    dt = dt.astype(F32)
    # the running sum of each position's log-decay inside its chunk
    cs = jnp.cumsum((dt * a.astype(F32)).reshape(bsz, lp // q, q, h),
                    axis=2).reshape(bsz, lp, h)
    # a step of zero (the padded tail) has log dt = -inf and no gradient
    stepped = dt > 0
    ldt = jnp.where(stepped, jnp.log(jnp.where(stepped, dt, 1.0)), -jnp.inf)
    y2 = _ssd(x.reshape(bsz, lp, h * p),
              b.reshape(bsz, lp, g * n).astype(dtype),
              c.reshape(bsz, lp, g * n).astype(dtype), cs, ldt, hb, q, n,
              _default_interpret() if interpret is None else interpret)
    return y2.reshape(bsz, lp, h, p)[:, :l]
