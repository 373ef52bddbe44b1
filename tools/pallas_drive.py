"""Compiled parity + timing drive for the Pallas kernels vs their XLA/jnp
oracles — run on a real TPU (also runs on CPU in interpret mode, slowly).

Round-1 VERDICT item 5: prove the kernels help compiled, or delete them
(the LSTM cell and the 2-bit quantizer went that way, on
``PALLAS_TPU_r02.jsonl``).
Round-2 VERDICT items 3/9: sweep >= 3 shapes per kernel (batch/seq/
channels) so "wired into hot paths" never rests on one point.  Each line
of output is a JSON record:
{kernel, shape, parity_max_abs_err, oracle_ms, pallas_ms, speedup}.

Each kernel has a ``*_case`` builder returning ``(oracle, pallas, args)``
— two jitted callables over the same arguments.  ``chip_smoke.py`` stage B
calls the same builders with ``interpret=False`` at the shapes the models
feed the kernels, so the oracles live in one place.

Usage:  python tools/pallas_drive.py                       # full sweep
        python tools/pallas_drive.py --only fused_bn_inference  # one kernel
        python tools/pallas_drive.py --only flash_fwd_tiles  # tile sweep
        python tools/pallas_drive.py --only flash_bwd_tiles  # the backward's
        python tools/pallas_drive.py --only grouped_mm_tiles  # routed layer's
        python tools/pallas_drive.py --only flash_win_tiles  # under a band
        python tools/pallas_drive.py --only grouped_mm_tiles_32  # 32 x 512
        python tools/pallas_drive.py --only grouped_mm_tiles_8  # 8 x 1,792
        python tools/pallas_drive.py --only ssd_scan  # Mamba-2 scan, by hb
        python tools/pallas_drive.py --only flash_edge_walk  # crossed tiles
        python tools/pallas_drive.py --only flash_layout  # operand layouts
        DT_FORCE_CPU=1 python tools/pallas_drive.py --small   # smoke
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timeit(fn, *args, iters=20):
    import jax
    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _leaves32(tree):
    import jax
    import numpy as np
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(tree)]


def _err(a, b):
    import numpy as np
    return max(float(np.max(np.abs(x - y))) if x.size else 0.0
               for x, y in zip(_leaves32(a), _leaves32(b)))


def rel_err(got, want):
    """Largest per-leaf ``max|got - want| / max|want|`` — the scale-free
    form of :func:`_err` a tolerance can be set against from the dtype."""
    import numpy as np
    return max(float(np.max(np.abs(x - y)) / (np.max(np.abs(y)) + 1e-6))
               if x.size else 0.0
               for x, y in zip(_leaves32(got), _leaves32(want)))


# ---------------------------------------------------------------------------
# cases: (oracle, pallas, args) per kernel.  ``interpret=None`` keeps each
# kernel's own default (interpreter off-TPU); ``False`` demands Mosaic.
# ---------------------------------------------------------------------------


def _bn_inputs(rng, shape, dt):
    import jax.numpy as jnp
    c = shape[-1]
    return (jnp.asarray(rng.randn(*shape), dt),
            jnp.asarray(rng.rand(c) + 0.5, jnp.float32),
            jnp.asarray(rng.randn(c), jnp.float32),
            jnp.asarray(rng.randn(c) * 0.1, jnp.float32),
            jnp.asarray(rng.rand(c) + 0.5, jnp.float32))


def bn_inference_case(rng, shape, dt, interpret=None):
    """Inference BN epilogue over the trailing channel axis of ``shape``."""
    import jax
    from dt_tpu.ops import nn
    from dt_tpu.ops.pallas import kernels
    x, gamma, beta, mean, var = _bn_inputs(rng, shape, dt)
    oracle = jax.jit(lambda x: nn.batch_norm(x, gamma, beta, mean, var,
                                             training=False)[0])
    pallas = jax.jit(lambda x: kernels.fused_bn_inference(
        x, gamma, beta, mean, var, interpret=interpret))
    return oracle, pallas, (x,)


def bn_train_case(rng, shape, dt, interpret=None):
    """TRAIN-mode fused BN (r5: VERDICT r4 weak 3) — fwd + bwd."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops import nn
    from dt_tpu.ops.pallas import kernels
    x, gamma, beta, mean, var = _bn_inputs(rng, shape, dt)

    def train_loss(fn):
        def loss(x, g, b):
            # cubed, not squared: sum(y^2) of a normalized y is constant in
            # x, so its dx is rounding noise and no oracle to compare with
            y, _, _ = fn(x, g, b)
            return jnp.sum(y * y * y)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    oracle = train_loss(lambda x, g, b: nn.batch_norm(
        x, g, b, mean, var, training=True))
    pallas = train_loss(lambda x, g, b: kernels.fused_bn_train(
        x, g, b, mean, var, 0.9, 1e-5, 256, interpret))
    return oracle, pallas, (x, gamma, beta)


def _chunked_full_attention(q, k, v, chunk=1024):
    """Memory-bounded causal-attention oracle for the 16k row: the naive
    S x S score matrix would be ~8.6 GB there, so queries stream in chunks
    (same math, O(S x chunk) live)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    B, S, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    cols = jnp.arange(S)

    def block(carry, idx):
        qi = lax.dynamic_slice_in_dim(q, idx * chunk, chunk, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        rows = idx * chunk + jnp.arange(chunk)
        mask = rows[:, None] >= cols[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    # remat each block: scan's backward would otherwise store every
    # block's S x chunk softmax (the very blowup this oracle exists to
    # avoid)
    _, outs = lax.scan(jax.checkpoint(block), 0, jnp.arange(S // chunk))
    return jnp.transpose(outs, (1, 0, 2, 3, 4)).reshape(q.shape)


def flash_case(rng, B, S, H, D, dt, interpret=None):
    """Causal flash attention fwd+bwd vs the full-attention oracle."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops.pallas import attention as attn
    from dt_tpu.parallel.ring_attention import full_attention
    qkv = tuple(jnp.asarray(rng.randn(B, S, H, D) * 0.3, dt)
                for _ in range(3))

    def attn_loss(f):
        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    # the naive oracle's float32 scores and what autodiff keeps of them
    # pass the device's memory from 2**30 scores on
    oracle_fn = (_chunked_full_attention if B * H * S * S >= 1 << 30
                 else lambda q, k, v: full_attention(q, k, v, causal=True))
    return (attn_loss(oracle_fn),
            attn_loss(lambda q, k, v: attn.flash_attention(
                q, k, v, causal=True, interpret=interpret)), qkv)


# the benchmark's two language-model cells (gpt2m-seq1024,
# granite4hm-b2-seq4096) and one shape at head size 128
FLASH_CELL_SHAPES = [(8, 1024, 16, 64), (2, 4096, 32, 64), (4, 2048, 8, 128)]
FLASH_SWEEP_TILES = (256, 512, 1024)


def _sweep_pairs(S):
    """The derived default (block None) first, 128 x 128, then every pair
    of FLASH_SWEEP_TILES that divides ``S``."""
    return [(None, None), (128, 128)] + [
        (bq, bk) for bq in FLASH_SWEEP_TILES for bk in FLASH_SWEEP_TILES
        if S % bq == 0 and S % bk == 0]


# the band's cell (laguna-xs2-ep8share-swa512-seq8192): its sliding layers,
# and beside them its full layers under plain causal
FLASH_WIN_SHAPE = (2, 8192, 64, 128, 512)
FLASH_WIN_FULL_SHAPE = (2, 8192, 48, 128)
FLASH_WIN_PAIRS = [(None, None), (128, 128), (256, 256), (512, 512),
                   (1024, 1024), (512, 256), (256, 512), (1024, 512),
                   (512, 128), (1024, 256)]


def _grid_steps(B, H, S, tq, tk, mask, bwd=False):
    """The steps of either kernel's grid: under a band its shortened axis."""
    if mask is None:
        return B * H * (S // tq) * (S // tk)
    if bwd:
        return B * H * (S // tk) * mask.query_steps(S, tq, tk)
    return B * H * (S // tq) * mask.key_steps(S, tq, tk)


def flash_tiles_sweep(rng, B, S, H, D, dt, iters=20, interpret=None,
                      mask=None, pairs=None):
    """The causal forward alone at every tile pair of FLASH_SWEEP_TILES,
    at 128 x 128 and at the derived default (block None):
    one record a pair, with its gap from the derived default's output.
    ``mask``: a static rule beside causal (a ``WindowMask``); ``pairs``:
    the pairs to time in place of ``_sweep_pairs``'."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops.pallas import attention as attn
    # the kernel's own (BH, S, D) layout: the models' transposes around
    # it are XLA's and not what the tiles move
    qkv = tuple(jnp.asarray(rng.randn(B * H, S, D) * 0.3, dt)
                for _ in range(3))

    if interpret is None:
        interpret = attn._default_interpret()
    derived = attn.forward_tiles(S, S, D, jnp.dtype(dt).itemsize, mask)
    pairs = pairs or _sweep_pairs(S)
    want = None
    for bq, bk in pairs:
        fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: attn._flash_fwd_pallas(
            q, k, v, scale=D ** -0.5, causal=True, block_q=bq, block_k=bk,
            interpret=interpret, mask=mask))
        got = fn(*qkv)
        want = got if want is None else want   # the derived pair runs first
        tq, tk = bq or derived[0], bk or derived[1]
        yield {"kernel": "flash_fwd_tiles" if mask is None
               else "flash_win_fwd_tiles",
               "shape": f"B{B}xS{S}xH{H}xD{D} {jnp.dtype(dt).name}"
               + (f" {mask}" if mask else ""),
               "block_q": tq, "block_k": tk, "derived": bq is None,
               "grid_steps": _grid_steps(B, H, S, tq, tk, mask),
               "vs_derived_max_abs_err": _err(got, want),
               "fwd_ms": round(_timeit(fn, *qkv, iters=iters), 4),
               "backend": jax.default_backend()}


def flash_bwd_tiles_sweep(rng, B, S, H, D, dt, iters=20, interpret=None,
                          mask=None, pairs=None):
    """The causal backward alone (delta in XLA and the one ``flash_bwd``
    call) at the derived default (block None), at 128 x 128 and at every
    tile pair of FLASH_SWEEP_TILES the compiler takes: one record a pair,
    with its gap from the derived default's gradients and the VMEM
    ``backward_vmem_bytes`` reckons for it."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops.pallas import attention as attn
    q, k, v, do = (jnp.asarray(rng.randn(B * H, S, D) * 0.3, dt)
                   for _ in range(4))
    if interpret is None:
        interpret = attn._default_interpret()
    itemsize = jnp.dtype(dt).itemsize
    out, lse = attn._flash_fwd_pallas(        # jitted where it is defined
        q, k, v, scale=D ** -0.5, causal=True, block_q=None, block_k=None,
        interpret=interpret, mask=mask)
    derived = attn.backward_tiles(S, S, D, itemsize, mask)
    pairs = pairs or _sweep_pairs(S)
    want = None
    for bq, bk in pairs:
        fn = jax.jit(lambda *a, bq=bq, bk=bk: attn._flash_bwd_pallas(
            *a, scale=D ** -0.5, causal=True, block_q=bq, block_k=bk,
            interpret=interpret, mask=mask))
        tq, tk = bq or derived[0], bk or derived[1]
        rec = {"kernel": "flash_bwd_tiles" if mask is None
               else "flash_win_bwd_tiles",
               "shape": f"B{B}xS{S}xH{H}xD{D} {jnp.dtype(dt).name}"
               + (f" {mask}" if mask else ""),
               "block_q": tq, "block_k": tk, "derived": bq is None,
               "grid_steps": _grid_steps(B, H, S, tq, tk, mask, bwd=True),
               "vmem_mb": round(attn.backward_vmem_bytes(
                   tq, tk, S, D, itemsize) / 2 ** 20, 1),
               "backend": jax.default_backend()}
        try:
            got = fn(q, k, v, out, lse, do)
            want = got if want is None else want  # the derived pair is first
            rec["vs_derived_max_abs_err"] = _err(got, want)
            rec["bwd_ms"] = round(_timeit(fn, q, k, v, out, lse, do,
                                          iters=iters), 4)
        except Exception as e:  # noqa: BLE001 — a pair Mosaic refuses
            rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        yield rec


# the five LM cells' flash calls (gpt2m, granite4hm, sdar30b's halves of
# 4,096 in blocks of 4, laguna's band of 512 and its full layers, keye30b's
# length without its selection): (B, S, H, D, rule), a rule a WindowMask's
# window or a BlockDiffusionMask's (half, block)
FLASH_EDGE_CASES = [(8, 1024, 16, 64, None), (2, 4096, 32, 64, None),
                    (2, 8192, 32, 128, (4096, 4)), (2, 8192, 64, 128, 512),
                    (2, 8192, 48, 128, None), (1, 16384, 32, 128, None)]


def flash_edge_walk_sweep(rng, B, S, H, D, dt, rule=None, subs=(0, 128, 256),
                          iters=20, interpret=None):
    """Both kernels at their derived tiles with crossed tiles computed
    whole (``sub`` 0) and walked in sub-blocks of 128 and of 256 (PERF.md
    section 6, PR 42): one record a ``sub``, with its gap from the whole
    tiles' results and the share of the run tiles' pairs it computes."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops.pallas import attention as attn
    mask = None if rule is None else attn.WindowMask(rule) \
        if isinstance(rule, int) else attn.BlockDiffusionMask(*rule)
    causal = not isinstance(mask, attn.BlockDiffusionMask)
    q, k, v, do = (jnp.asarray(rng.randn(B * H, S, D) * 0.3, dt)
                   for _ in range(4))
    if interpret is None:
        interpret = attn._default_interpret()
    itemsize = jnp.dtype(dt).itemsize
    kw = dict(scale=D ** -0.5, causal=causal, interpret=interpret, mask=mask)
    want = None
    for sub in subs:
        fwd = jax.jit(lambda q, k, v, sub=sub: attn._flash_fwd_pallas(
            q, k, v, block_q=None, block_k=None, sub=sub, **kw))
        bwd = jax.jit(lambda *a, sub=sub: attn._flash_bwd_pallas(
            *a, sub=sub, **kw))
        out, lse = fwd(q, k, v)
        got = (out, lse) + tuple(bwd(q, k, v, out, lse, do))
        want = want or got                      # whole tiles run first
        rec = {"kernel": "flash_edge_walk", "sub": sub,
               "shape": f"B{B}xS{S}xH{H}xD{D} {jnp.dtype(dt).name}"
               + (f" {mask}" if mask else ""),
               "vs_whole_max_abs_err": _err(got, want),
               "fwd_ms": round(_timeit(fwd, q, k, v, iters=iters), 4),
               "bwd_ms": round(_timeit(bwd, q, k, v, out, lse, do,
                                       iters=iters), 4),
               "backend": jax.default_backend()}
        for name, tiles in (("fwd", attn.forward_tiles),
                            ("bwd", attn.backward_tiles)):
            tq, tk = tiles(S, S, D, itemsize, mask)
            run, _, computed = attn.computed_tiles(mask, causal, S, S, tq,
                                                   tk, sub)
            rec[f"{name}_tile"] = tq
            rec[f"{name}_pairs_computed_pct"] = round(100 * computed / run,
                                                      2)
        yield rec


def _device_ms(fn, *args, iters=8):
    """The median device time of the longest-running operation of ``fn``
    (the kernel, when ``fn`` is one kernel call) over ``iters`` calls, from
    a ``jax.profiler`` trace read through ``benchmark/xplane.py`` (a
    trace without a device line, a CPU's, raises)."""
    import shutil
    import statistics
    import tempfile
    import jax
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import xplane
    jax.block_until_ready(fn(*args))
    where = tempfile.mkdtemp(prefix="flash_layout_")
    try:
        with jax.profiler.trace(where):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        by_name = {}
        for plane, line, events in xplane.load(xplane.find(where)):
            if plane.startswith("/device:") and line == xplane.OPS_LINE:
                for name, _, dur, _ in events:
                    by_name.setdefault(name, []).append(dur)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    if not by_name:
        raise RuntimeError("the trace holds no device operations")
    return statistics.median(max(by_name.values(), key=sum)) / 1e6


# the three cells with heads of 128 (laguna-xs2...'s sliding and full layers,
# sdar30b..., keye30b...): batch, positions, query heads, key-value heads,
# the rule
FLASH_LAYOUT_CASES = [(2, 8192, 64, 8, 512), (2, 8192, 48, 8, None),
                      (2, 8192, 32, 4, (4096, 4)), (1, 16384, 32, 4, "sel")]


def flash_layout_sweep(rng, B, S, H, KV, rule, dt, D=128, iters=8,
                       interpret=None):
    """Both kernels alone with ``(B * H, S, D)`` operands, heads after the
    batch, and with ``(B, S, H * D)`` operands, a block one head's 128
    columns of rows ``H * D`` apart (PERF.md section 6, PR 45): one record
    a layout, device event times (the host clock on a CPU) and the gap
    between the two layouts' results."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops.pallas import attention as attn
    selected = rule == "sel"
    mask = attn.SelectedKeysMask() if selected else None if rule is None \
        else attn.WindowMask(rule) if isinstance(rule, int) \
        else attn.BlockDiffusionMask(*rule)
    causal = not isinstance(mask, attn.BlockDiffusionMask)
    if interpret is None:
        interpret = attn._default_interpret()
    mk = lambda h: jnp.asarray(rng.randn(B, S, h, D) * 0.3, dt)  # noqa: E731
    q, k, v, do = mk(H), mk(KV), mk(KV), mk(H)
    sel = None
    if selected:    # every query its own key and the 2,048 before it
        pos = jnp.arange(S)
        near = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] > pos[:, None] - 2048)
        sel = attn.pack_selection(jnp.broadcast_to(near, (B, S, S)))
    layouts = {
        "bhsd": (None, lambda x: jnp.moveaxis(x, 2, 1).reshape(
            -1, S, D), lambda x3: jnp.moveaxis(
                x3.reshape(B, -1, S, D), 1, 2)),
        "bshd": (H, lambda x: x.reshape(B, S, -1),
                 lambda x3: x3.reshape(B, S, -1, D))}
    want = None
    for name, (heads, to3, back) in layouts.items():
        kw = dict(scale=D ** -0.5, causal=causal, interpret=interpret,
                  mask=mask, selection=sel, heads=heads)
        fwd = jax.jit(lambda q, k, v, kw=kw: attn._flash_fwd_pallas(
            q, k, v, block_q=None, block_k=None, **kw))
        bwd = jax.jit(lambda *a, kw=kw: attn._flash_bwd_pallas(*a, **kw))
        args = tuple(to3(x) for x in (q, k, v))
        out, lse = fwd(*args)
        grads = bwd(*args, out, lse, to3(do))
        got = (back(out), lse) + tuple(back(g) for g in grads)
        want = want or got
        time_of = _timeit if jax.default_backend() == "cpu" else _device_ms
        yield {"kernel": "flash_layout", "layout": name,
               "shape": f"B{B}xS{S}xH{H}xKV{KV}xD{D} {jnp.dtype(dt).name}"
               + (f" {mask}" if mask else ""),
               "vs_bhsd_max_abs_err": _err(got, want),
               "fwd_ms": round(time_of(fwd, *args, iters=iters), 4),
               "bwd_ms": round(time_of(bwd, *args, out, lse, to3(do),
                                       iters=iters), 4),
               "clock": "host" if time_of is _timeit else "device",
               "backend": jax.default_backend()}


# the two routed cells' buffers (sdar30b..., keye30b...) against gate/up and
# down, 16 experts held
GROUPED_CELL_SHAPES = [(49152, 2048, 768), (24576, 2048, 768),
                       (49152, 768, 2048), (24576, 768, 2048)]
GROUPED_SWEEP_TILES = (256, 512, 1024)
# the third routed cell's buffer (laguna-xs2...): 32 experts held, of 512
GROUPED_32_SHAPES = [(24576, 2048, 512), (24576, 512, 2048)]
# the fourth routed cell's buffer (lfm2-8b-a1b...): 8 experts held, of 1,792
GROUPED_8_SHAPES = [(24576, 2048, 1792), (24576, 1792, 2048)]


def grouped_loads(rng, m, groups):
    """Three ways to fill ``m`` rows: ``even`` (every boundary on a tile's
    edge: the grid's spare visits all run masked), ``ragged`` (random sizes,
    every boundary inside a tile) and ``padded`` (a third of the rows
    loaded, unevenly, the rest in the last group: the routed layer's
    buffer under a clumped load)."""
    import numpy as np
    ragged = rng.multinomial(m - groups, np.ones(groups) / groups) + 1
    held = rng.multinomial(m // 3, rng.dirichlet(np.ones(groups) * 0.7))
    held[-1] += m - held.sum()
    return {"even": np.full(groups, m // groups), "ragged": ragged,
            "padded": held}


def grouped_mm_tiles_sweep(rng, m, k, n, dt, groups=16, iters=30,
                           interpret=None, tiles=GROUPED_SWEEP_TILES):
    """The three grouped products (value, ``d_lhs``, ``d_rhs``) one at a
    time at ``m`` rows against ``groups`` matrices ``k x n``: XLA's kernel
    for ``jax.lax.ragged_dot`` and its transposes, megablox's ``gmm`` at the
    derived tile (the value and the turned product only: its ``tgmm`` wants
    a transposed copy), and this repo's kernels at every row tile of
    GROUPED_SWEEP_TILES plus the derived one under each of
    ``grouped_loads``.  One record a timing, with its share of the peak for
    ``2 m k n`` operations (host clock; the jitted call holds the visit
    tables' few small operations too)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dt_tpu.ops.pallas import grouped
    if interpret is None:
        interpret = grouped._default_interpret()
    lhs = jnp.asarray(rng.randn(m, k) * 0.3, dt)
    rhs = jnp.asarray(rng.randn(groups, k, n) * 0.05, dt)
    d_out = jnp.asarray(rng.randn(m, n) * 0.3, jnp.float32)
    loads = {name: jnp.asarray(v, jnp.int32)
             for name, v in grouped_loads(rng, m, groups).items()}
    a, b = lhs.dtype.itemsize, rhs.dtype.itemsize
    derived = {"value": grouped.row_tile(m, k, n, a, b, 4),
               "d_lhs": grouped.row_tile(m, n, k, 4, b, a),
               "d_rhs": grouped.row_tile(m, k, n, a, 4, b, transposed=True)}
    ragged = lambda x, w, gs: jax.lax.ragged_dot(  # noqa: E731
        x, w, gs, preferred_element_type=jnp.float32)
    xla = {
        "value": (jax.jit(ragged), (lhs, rhs)),
        "d_lhs": (jax.jit(lambda do, w, gs: jax.vjp(
            lambda x: ragged(x, w, gs), lhs)[1](do)[0]), (d_out, rhs)),
        "d_rhs": (jax.jit(lambda x, do, gs: jax.vjp(
            lambda w: ragged(x, w, gs), rhs)[1](do)[0]), (lhs, d_out)),
    }

    def ours(product, tm):
        if product == "d_rhs":
            return jax.jit(lambda x, do, gs: grouped._grouped_mm_t(
                x, do, gs, out_dtype=rhs.dtype, tm=tm, interpret=interpret))
        turned = product == "d_lhs"
        return jax.jit(lambda x, w, gs: grouped._grouped_mm(
            x, w, gs, turned=turned, tm=tm, interpret=interpret,
            out_dtype=lhs.dtype if turned else jnp.dtype(jnp.float32)))

    def record(product, which, tm, load, fn, args, want=None):
        rec = {"kernel": "grouped_mm_tiles", "product": product,
               "shape": f"M{m}xK{k}xN{n}xG{groups} {jnp.dtype(dt).name}",
               "which": which, "tm": tm, "load": load,
               "derived": tm == derived[product],
               "backend": jax.default_backend()}
        try:
            got = fn(*args, loads[load])
            if want is not None:
                rec["vs_xla_rel_err"] = rel_err(got, want)
            ms = _timeit(fn, *args, loads[load], iters=iters)
            rec["ms"] = round(ms, 4)
            rec["pct_of_197_tflops"] = round(
                100 * 2 * m * k * n / (ms * 1e-3) / 197e12, 1)
        except Exception as e:  # noqa: BLE001 — a tile Mosaic refuses
            rec["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            got = None
        return rec, got

    for product, (fn, args) in xla.items():
        rec, want = record(product, "ragged_dot", None, "ragged", fn, args)
        yield rec
        if product != "d_rhs" and not interpret:
            from jax.experimental.pallas.ops.tpu.megablox import gmm
            turned = product == "d_lhs"
            tm = derived[product]
            mega = jax.jit(lambda x, w, gs, turned=turned, tm=tm: gmm(
                x, w, gs, jnp.float32, (tm, *x.shape[1:], w.shape[
                    1 if turned else 2]), transpose_rhs=turned))
            yield record(product, "megablox", tm, "ragged", mega, args,
                         want)[0]
        for tm in sorted({*tiles, derived[product]} - {None}):
            if m % tm:
                continue
            for load in (loads if tm == derived[product] else ("ragged",)):
                yield record(product, "pallas", tm, load, ours(product, tm),
                             args, want if load == "ragged" else None)[0]


# (B, L, H, P, G, N, chunk): the hybrid cell's state-space layers
# (granite-4.0-h-micro, benchmark/configs; nine of them a step)
SSD_CELL_SHAPE = (2, 4096, 64, 64, 1, 128, 256)
SSD_SWEEP_HEAD_BLOCKS = (4, 8, 16)


def _ssd_inputs(rng, b, l, h, p, g, n, dt):
    """``ssd_scan``'s five inputs at the sizes a trained mixer feeds it:
    steps log-uniform in 0.001..0.1, ``a`` in -16..-1 (Mamba-2's own
    initialisation, ``models/hybrid_lm.py``)."""
    import jax.numpy as jnp
    return (jnp.asarray(rng.randn(b, l, h, p), dt),
            jnp.asarray(_np_exp_uniform(rng, (b, l, h)), jnp.float32),
            jnp.asarray(-rng.uniform(1.0, 16.0, (h,)), jnp.float32),
            jnp.asarray(rng.randn(b, l, g, n), dt),
            jnp.asarray(rng.randn(b, l, g, n), dt))


def _np_exp_uniform(rng, shape, lo=1e-3, hi=0.1):
    import numpy as np
    return np.exp(rng.uniform(np.log(lo), np.log(hi), shape))


def _ssd_passes(scan, d_out):
    """(forward alone, forward and the five gradients) of ``scan``, jitted
    over its five inputs."""
    import jax
    fwd = jax.jit(scan)

    def both(*t):
        y, pull = jax.vjp(scan, *t)
        return y, pull(d_out.astype(y.dtype))
    return fwd, jax.jit(both)


def _ssd_setup(rng, b, l, h, p, g, n, chunk, dt):
    """What the case and the sweep share: the inputs, the cotangent, the
    chunk's positions, the derived head block and the oracle (the XLA body
    in float32 on the same numbers, forward and gradients)."""
    import jax.numpy as jnp
    from dt_tpu.ops import ssm
    from dt_tpu.ops.pallas import ssd
    args = _ssd_inputs(rng, b, l, h, p, g, n, dt)
    d_out = jnp.asarray(rng.randn(b, l, h, p), jnp.float32)
    q = min(chunk, l)
    hb = ssd.head_block(h, g, p, n, q, jnp.dtype(dt).itemsize)
    oracle = _ssd_passes(lambda *t: ssm.ssd_scan_xla(*(
        v.astype(jnp.float32) for v in t), chunk=chunk), d_out)[1]
    return args, d_out, q, hb, oracle


def ssd_scan_case(rng, b, l, h, p, g, n, chunk, dt, interpret=None):
    """The scan and its five gradients through the kernels, in ``dt``,
    against the XLA body in float32 on the same numbers."""
    from dt_tpu.ops.pallas import ssd
    args, d_out, q, hb, oracle = _ssd_setup(rng, b, l, h, p, g, n, chunk, dt)
    assert hb is not None, "the kernels do not take this shape"
    pallas = _ssd_passes(lambda *t: ssd.ssd_scan_pallas(
        *t, q=q, hb=hb, interpret=interpret), d_out)[1]
    return oracle, pallas, args


def ssd_scan_sweep(rng, b, l, h, p, g, n, chunk, dt, iters=20,
                   interpret=None):
    """Forward alone and forward with backward of the scan on the host's
    clock: ``ssd_scan_xla`` and the kernels at each head block of
    ``SSD_SWEEP_HEAD_BLOCKS`` and the derived one, each against the XLA body
    in float32.  One record each; ``bwd_ms`` is the difference of the two
    timings."""
    import jax
    import jax.numpy as jnp
    from dt_tpu.ops import ssm
    from dt_tpu.ops.pallas import ssd
    args, d_out, q, derived, oracle = _ssd_setup(rng, b, l, h, p, g, n,
                                                 chunk, dt)
    want = oracle(*args)
    jax.block_until_ready(want)
    paths = [("xla", None, lambda *t: ssm.ssd_scan_xla(*t, chunk=chunk))]
    for hb in sorted({*SSD_SWEEP_HEAD_BLOCKS, derived} - {None}):
        if (h // g) % hb == 0 and hb * p % 128 == 0:
            paths.append(("pallas", hb, lambda *t, hb=hb: ssd.ssd_scan_pallas(
                *t, q=q, hb=hb, interpret=interpret)))
    # the products a layer's forward needs: C B^T a chunk and group, and a
    # head and chunk the square against x, the state and the state's part
    flops = 2 * b * (l // q) * (g * q * q * n + h * (q * q * p
                                                     + 2 * q * p * n))
    for which, hb, scan in paths:
        rec = {"kernel": "ssd_scan", "which": which, "hb": hb,
               "derived": hb == derived and which == "pallas",
               "shape": f"B{b}xL{l}xH{h}xP{p}xG{g}xN{n} chunk{chunk} "
                        f"{jnp.dtype(dt).name}",
               "backend": jax.default_backend()}
        try:
            fwd, both = _ssd_passes(scan, d_out)
            got = both(*args)
            rec["vs_f32_xla_rel_err"] = {
                name: round(rel_err(a, b), 6) for name, a, b in zip(
                    ("y", "dx", "ddt", "da", "db", "dc"),
                    (got[0], *got[1]), (want[0], *want[1]))}
            rec["fwd_ms"] = round(_timeit(fwd, *args, iters=iters), 4)
            rec["fwd_bwd_ms"] = round(_timeit(both, *args, iters=iters), 4)
            rec["bwd_ms"] = round(rec["fwd_bwd_ms"] - rec["fwd_ms"], 4)
            rec["fwd_pct_of_197_tflops"] = round(
                100 * flops / (rec["fwd_ms"] * 1e-3) / 197e12, 1)
        except Exception as e:  # noqa: BLE001 — a head block Mosaic refuses
            rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        yield rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (CPU interpret smoke)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--only", default=None,
                    help="comma list of kernel names to run")
    args = ap.parse_args()

    from dt_tpu.config import maybe_force_cpu, enable_compilation_cache
    maybe_force_cpu()
    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    rng = np.random.RandomState(0)
    only = set(args.only.split(",")) if args.only else None

    def wanted(name):
        return only is None or name in only

    def emit(kernel, shape, case):
        # print per-record, flushed: a crash in a later kernel must not
        # lose earlier evidence (a round-2 lesson)
        oracle, pallas, a = case
        rec = {"kernel": kernel, "shape": shape,
               "parity_max_abs_err": _err(oracle(*a), pallas(*a)),
               "oracle_ms": round(_timeit(oracle, *a, iters=args.iters), 3),
               "pallas_ms": round(_timeit(pallas, *a, iters=args.iters), 3),
               "backend": backend}
        rec["speedup"] = round(rec["oracle_ms"] / rec["pallas_ms"], 3) \
            if rec["pallas_ms"] else None
        print(json.dumps(rec), flush=True)

    dt = jnp.float32 if args.small else jnp.bfloat16

    # ---- BN inference epilogue + train-mode fused BN ---------------------
    if wanted("fused_bn_inference"):
        for N, HW, C in ([(4, 8, 64)] if args.small else
                         [(64, 56, 256),    # round-2 point
                          (32, 112, 64),    # early-layer: big spatial
                          (8, 28, 512)]):   # late-layer: channel-heavy
            shape = f"{N}x{HW}x{HW}x{C} {dt.__name__}"
            emit("fused_bn_inference", shape,
                 bn_inference_case(rng, (N, HW, HW, C), dt))
            emit("fused_bn_train_fwd_bwd", shape,
                 bn_train_case(rng, (N, HW, HW, C), dt))

    # ---- flash attention fwd+bwd vs full-attention oracle ---------------
    if wanted("flash_attention_fwd_bwd"):
        for B, S, H, D in ([(1, 256, 2, 64)] if args.small else
                           FLASH_CELL_SHAPES[:2] +
                           [(4, 2048, 8, 128),   # round-2 point
                            (8, 1024, 8, 128),   # shorter seq, bigger batch
                            (1, 8192, 8, 128),   # long-context: O(S^2) oracle
                            (1, 16384, 8, 128)]):  # VERDICT r4 item 2
            emit("flash_attention_fwd_bwd",
                 f"B{B}xS{S}xH{H}xD{D} {dt.__name__}",
                 flash_case(rng, B, S, H, D, dt))

    # ---- the flash forward alone, by tile pair (PERF.md, PR 29) ----------
    if wanted("flash_fwd_tiles"):
        for B, S, H, D in ([(1, 512, 2, 64)] if args.small else
                           FLASH_CELL_SHAPES):
            for rec in flash_tiles_sweep(rng, B, S, H, D, dt,
                                         iters=args.iters):
                print(json.dumps(rec), flush=True)

    # ---- the flash backward alone, by tile pair (PERF.md, PR 31) ---------
    if wanted("flash_bwd_tiles"):
        for B, S, H, D in ([(1, 512, 2, 64)] if args.small else
                           FLASH_CELL_SHAPES):
            for rec in flash_bwd_tiles_sweep(rng, B, S, H, D, dt,
                                             iters=args.iters):
                print(json.dumps(rec), flush=True)

    # ---- the routed layer's grouped products, by row tile (PR 38) --------
    if wanted("grouped_mm_tiles"):
        for m, k, n in ([(512, 128, 256)] if args.small else
                        GROUPED_CELL_SHAPES):
            for rec in grouped_mm_tiles_sweep(rng, m, k, n, dt,
                                              groups=4 if args.small else 16,
                                              iters=args.iters):
                print(json.dumps(rec), flush=True)

    # ---- both flash kernels under a band, by tile pair (PR 41) ------------
    if wanted("flash_win_tiles"):
        from dt_tpu.ops.pallas.attention import WindowMask
        B, S, H, D, W = (1, 512, 2, 64, 200) if args.small \
            else FLASH_WIN_SHAPE
        pairs = [(None, None), (128, 128)] if args.small else FLASH_WIN_PAIRS
        for sweep in (flash_tiles_sweep, flash_bwd_tiles_sweep):
            for rec in sweep(rng, B, S, H, D, dt, iters=args.iters,
                             mask=WindowMask(W), pairs=pairs):
                print(json.dumps(rec), flush=True)
            if not args.small:      # the cell's full layers, as derived
                for rec in sweep(rng, *FLASH_WIN_FULL_SHAPE, dt,
                                 iters=args.iters, pairs=[(None, None)]):
                    print(json.dumps(rec), flush=True)

    # ---- both flash kernels, crossed tiles whole or walked (PR 42) -------
    if wanted("flash_edge_walk"):
        for B, S, H, D, rule in ([(1, 512, 2, 64, None), (1, 512, 2, 64, 256),
                                  (1, 512, 2, 64, (256, 4))] if args.small
                                 else FLASH_EDGE_CASES):
            for rec in flash_edge_walk_sweep(rng, B, S, H, D, dt, rule,
                                             iters=args.iters):
                print(json.dumps(rec), flush=True)

    # ---- both flash kernels by operand layout at head size 128 (PR 45) ---
    if wanted("flash_layout"):
        for B, S, H, KV, rule in ([(1, 256, 4, 2, None), (1, 256, 4, 1, 128),
                                   (1, 256, 2, 2, (128, 4)),
                                   (1, 256, 4, 2, "sel")] if args.small
                                  else FLASH_LAYOUT_CASES):
            for rec in flash_layout_sweep(rng, B, S, H, KV, rule, dt,
                                          iters=min(args.iters, 8)):
                print(json.dumps(rec), flush=True)

    # ---- the grouped products at 32 groups of 2,048 x 512 (PR 41) --------
    if wanted("grouped_mm_tiles_32"):
        for m, k, n in ([(512, 128, 256)] if args.small else
                        GROUPED_32_SHAPES):
            for rec in grouped_mm_tiles_sweep(rng, m, k, n, dt,
                                              groups=4 if args.small else 32,
                                              iters=args.iters,
                                              tiles=(128, 256, 512)):
                print(json.dumps(rec), flush=True)

    # ---- the grouped products at 8 groups of 2,048 x 1,792 (PR 43) -------
    if wanted("grouped_mm_tiles_8"):
        for m, k, n in ([(512, 128, 256)] if args.small else
                        GROUPED_8_SHAPES):
            for rec in grouped_mm_tiles_sweep(rng, m, k, n, dt,
                                              groups=4 if args.small else 8,
                                              iters=args.iters,
                                              tiles=(128, 256)):
                print(json.dumps(rec), flush=True)

    # ---- the Mamba-2 scan, forward and backward, by head block (PR 40) ---
    if wanted("ssd_scan"):
        shape = (1, 256, 2, 64, 1, 128, 128) if args.small else SSD_CELL_SHAPE
        for rec in ssd_scan_sweep(rng, *shape, dt, iters=args.iters):
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
