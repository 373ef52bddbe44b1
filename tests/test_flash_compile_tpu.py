"""The flash forward and backward compiled by the TPU's own compiler for a described
v5e, no chip attached: Mosaic refuses what the Pallas interpreter takes (a
tile that does not fit VMEM, a store that is not lane-aligned), and a
refusal here costs no chip time.  Nothing runs; no time is read."""

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from dt_tpu.ops.pallas import attention as attn


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# the benchmark's two cells, head size 128 in float32, and a length that
# only 128 divides
SHAPES = [
    (8 * 16, 1024, 64, jnp.bfloat16),
    (2 * 32, 4096, 64, jnp.bfloat16),
    (8, 2048, 128, jnp.float32),
    (4, 640, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("bh,s,d,dtype", SHAPES)
def test_mosaic_takes_the_derived_tiles(one_chip, bh, s, d, dtype):
    x = jax.ShapeDtypeStruct((bh, s, d), dtype, sharding=one_chip)
    fwd = jax.jit(lambda q, k, v: attn._flash_fwd_pallas(
        q, k, v, scale=d ** -0.5, causal=True, block_q=None, block_k=None,
        interpret=False))
    compiled = fwd.lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bh,s,d,dtype", SHAPES)
def test_mosaic_takes_the_backward(one_chip, bh, s, d, dtype):
    """The backward at its own derived tiles, and under the name the
    benchmark's ``kernel.flash_bwd_*`` find it by."""
    x = jax.ShapeDtypeStruct((bh, s, d), dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, s), jnp.float32, sharding=one_chip)
    bwd = jax.jit(lambda q, k, v, o, lse, do: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, scale=d ** -0.5, causal=True, interpret=False))
    text = bwd.lower(x, x, x, x, lse, x).compile().as_text()
    calls = [ln.split("=")[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert calls and all("flash_bwd" in c and "attn" not in c
                         for c in calls), calls


# under the block-diffusion mask rule: the new cell's shape (32 heads of 128
# over [xt ; x0] of 8,192 positions, blocks of 4) and a half that only 128
# divides, with a block length that is no power of two
MASKED = [
    (2 * 32, 4096, 4, 128, jnp.bfloat16),
    (4, 640, 5, 64, jnp.float32),
]


@pytest.mark.parametrize("bh,half,block,d,dtype", MASKED)
def test_mosaic_takes_both_kernels_under_the_mask_rule(one_chip, bh, half,
                                                       block, d, dtype):
    """Forward and backward pruned, masked and fetched by the rule, under
    the names the benchmark's ``kernel.flash_*.bd`` find them by."""
    rule = attn.BlockDiffusionMask(half, block)
    x = jax.ShapeDtypeStruct((bh, 2 * half, d), dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, 2 * half), jnp.float32,
                               sharding=one_chip)
    fwd = jax.jit(lambda q, k, v: attn._flash_fwd_pallas(
        q, k, v, scale=d ** -0.5, causal=False, block_q=None, block_k=None,
        interpret=False, mask=rule))
    bwd = jax.jit(lambda q, k, v, o, lse, do: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, scale=d ** -0.5, causal=False, interpret=False,
        mask=rule))
    for text, name in ((fwd.lower(x, x, x).compile().as_text(),
                        "flash_fwd_bd"),
                       (bwd.lower(x, x, x, x, lse, x).compile().as_text(),
                        "flash_bwd_bd")):
        calls = [ln.split("=")[0] for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "custom-call(" in ln]
        assert calls and all(name in c for c in calls), calls


# under the band rule: the mixed-attention cell's sliding layers (64 heads of
# 128 over 8,192 positions, a window of 512), and a window that is no whole
# tile over a length that only 128 divides
WINDOWED = [
    (2 * 64, 8192, 512, 128, jnp.bfloat16),
    (4, 640, 200, 64, jnp.float32),
]


@pytest.mark.parametrize("bh,s,window,d,dtype", WINDOWED)
def test_mosaic_takes_both_kernels_under_the_band_rule(one_chip, bh, s,
                                                       window, d, dtype):
    """Forward and backward over the shortened grid axis, under the names
    the benchmark's ``kernel.flash_*.win`` find them by, which hold neither
    ``flash_fwd`` nor ``flash_bwd``."""
    rule = attn.WindowMask(window)
    x = jax.ShapeDtypeStruct((bh, s, d), dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, s), jnp.float32, sharding=one_chip)
    fwd = jax.jit(lambda q, k, v: attn._flash_fwd_pallas(
        q, k, v, scale=d ** -0.5, causal=True, block_q=None, block_k=None,
        interpret=False, mask=rule))
    bwd = jax.jit(lambda q, k, v, o, lse, do: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, scale=d ** -0.5, causal=True, interpret=False,
        mask=rule))
    for text, name in ((fwd.lower(x, x, x).compile().as_text(),
                        "flash_win_fwd"),
                       (bwd.lower(x, x, x, x, lse, x).compile().as_text(),
                        "flash_win_bwd")):
        calls = [ln.split("=")[0] for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "custom-call(" in ln]
        assert calls and all(name in c and "flash_bwd" not in c
                             and "flash_fwd" not in c for c in calls), calls


# under the selection rule beside causal: the sparse-attention cell's shape
# (32 heads of 128 over 16,384 positions, the selection as two bitmaps of
# four groups) and a length under one group that only 128 divides
SELECTED = [
    (1, 32, 16384, 128, jnp.bfloat16),
    (2, 2, 640, 64, jnp.float32),
]


@pytest.mark.parametrize("b,h,s,d,dtype", SELECTED)
def test_mosaic_takes_both_kernels_under_a_selection(one_chip, b, h, s, d,
                                                     dtype):
    """Forward and backward with the bitmap block and the scalar-prefetch
    fate table, under the names the benchmark's ``kernel.flash_*.sel`` find
    them by."""
    shape = lambda *a: jax.ShapeDtypeStruct(*a, sharding=one_chip)  # noqa: E731
    x = shape((b * h, s, d), dtype)
    lse = shape((b * h, s), jnp.float32)
    words = shape((b, -(-s // attn.SEL_GROUP), s, 128), jnp.int32)
    sel = attn.Selection(words, words, shape((b, s // 128, s // 128),
                                             jnp.bool_))
    rule = attn.SelectedKeysMask()
    fwd = jax.jit(lambda q, k, v, sel: attn._flash_fwd_pallas(
        q, k, v, scale=d ** -0.5, causal=True, block_q=None, block_k=None,
        interpret=False, mask=rule, selection=sel))
    bwd = jax.jit(lambda q, k, v, o, lse, do, sel: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, scale=d ** -0.5, causal=True, interpret=False,
        mask=rule, selection=sel))
    for text, name in ((fwd.lower(x, x, x, sel).compile().as_text(),
                        "flash_fwd_sel"),
                       (bwd.lower(x, x, x, x, lse, x, sel).compile().as_text(),
                        "flash_bwd_sel")):
        calls = [ln.split("=")[0] for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "custom-call(" in ln]
        assert calls and all(name in c for c in calls), calls


# with crossed tiles walked in sub-blocks (PR 42): the six kernels at the
# five LM cells' shapes (gpt2m, granite4hm, sdar30b, laguna's band and its
# full layers, keye30b), at sub-blocks of either size the sweep tried
WALKED = [
    ("gpt2m", 8 * 16, 1024, 64, None, True),
    ("granite4hm", 2 * 32, 4096, 64, None, True),
    ("sdar30b", 2 * 32, 8192, 128, attn.BlockDiffusionMask(4096, 4), False),
    ("laguna.win", 2 * 64, 8192, 128, attn.WindowMask(512), True),
    ("laguna.full", 2 * 48, 8192, 128, None, True),
    ("keye30b", 32, 16384, 128, attn.SelectedKeysMask(), True),
]


@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("cell,bh,s,d,rule,causal", WALKED,
                         ids=[c[0] for c in WALKED])
def test_mosaic_takes_the_walk_at_the_cells_shapes(one_chip, cell, bh, s, d,
                                                   rule, causal, sub):
    """Both passes compile with the walk's unrolled row blocks in them,
    inside the scoped VMEM the calls ask for (twice ``VMEM_BUDGET``: a
    sub-block's scores are smaller than a tile's), under the names the
    benchmark's ``kernel.flash_*`` find them by."""
    shape = lambda *a: jax.ShapeDtypeStruct(*a, sharding=one_chip)  # noqa: E731
    x, lse = shape((bh, s, d), jnp.bfloat16), shape((bh, s), jnp.float32)
    selected = isinstance(rule, attn.SelectedKeysMask)
    sel = ()
    if selected:
        words = shape((1, -(-s // attn.SEL_GROUP), s, 128), jnp.int32)
        sel = (attn.Selection(words, words, shape((1, s // 128, s // 128),
                                                  jnp.bool_)),)
    for tiles in (attn.forward_tiles, attn.backward_tiles):
        b = tiles(s, s, d, 2, None if selected else rule)[0]
        assert attn.crossed_kinds(rule, causal, s, s, b, b, sub)
    kw = dict(scale=d ** -0.5, causal=causal, interpret=False, mask=rule,
              sub=sub)
    fwd = jax.jit(lambda q, k, v, *sel: attn._flash_fwd_pallas(
        q, k, v, block_q=None, block_k=None, selection=(sel or (None,))[0],
        **kw))
    bwd = jax.jit(lambda q, k, v, o, lse, do, *sel: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, selection=(sel or (None,))[0], **kw))
    texts = (fwd.lower(x, x, x, *sel).compile().as_text(),
             bwd.lower(x, x, x, x, lse, x, *sel).compile().as_text())
    names = ("", "flash_bwd") if rule is None else (
        attn._kernel_name(rule, "fwd"), attn._kernel_name(rule, "bwd"))
    for text, name in zip(texts, names):
        calls = [ln.split("=")[0] for ln in text.splitlines()
                 if "tpu_custom_call" in ln and "custom-call(" in ln]
        assert calls and all(name in c for c in calls), calls


# with fewer key-value heads than query heads (PR 44): the index maps read
# key-value row ``b // rep``; the five LM cells with grouped heads, by
# (batch x query heads, batch x key-value heads)
GROUPED = [
    ("granite4hm", 2 * 32, 2 * 8, 4096, 64, None, True),
    ("sdar30b", 2 * 32, 2 * 4, 8192, 128, attn.BlockDiffusionMask(4096, 4),
     False),
    ("laguna.win", 2 * 64, 2 * 8, 8192, 128, attn.WindowMask(512), True),
    ("laguna.full", 2 * 48, 2 * 8, 8192, 128, None, True),
    ("keye30b", 32, 4, 16384, 128, attn.SelectedKeysMask(), True),
    ("lfm2", 32, 8, 16384, 64, None, True),
]


@pytest.mark.parametrize("cell,bh,bkv,s,d,rule,causal", GROUPED,
                         ids=[c[0] for c in GROUPED])
def test_mosaic_takes_grouped_heads_at_the_cells_shapes(one_chip, cell, bh,
                                                        bkv, s, d, rule,
                                                        causal):
    """Both passes compile with (B * KV, S, D) keys and values under every
    mask rule, and the backward's dk, dv come out one a query row."""
    shape = lambda *a: jax.ShapeDtypeStruct(*a, sharding=one_chip)  # noqa: E731
    x, kv = shape((bh, s, d), jnp.bfloat16), shape((bkv, s, d), jnp.bfloat16)
    lse = shape((bh, s), jnp.float32)
    sel = ()
    if isinstance(rule, attn.SelectedKeysMask):
        words = shape((1, -(-s // attn.SEL_GROUP), s, 128), jnp.int32)
        sel = (attn.Selection(words, words, shape((1, s // 128, s // 128),
                                                  jnp.bool_)),)
    kw = dict(scale=d ** -0.5, causal=causal, interpret=False, mask=rule)
    fwd = jax.jit(lambda q, k, v, *sel: attn._flash_fwd_pallas(
        q, k, v, block_q=None, block_k=None, selection=(sel or (None,))[0],
        **kw))
    bwd = jax.jit(lambda q, k, v, o, lse, do, *sel: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, selection=(sel or (None,))[0], **kw))
    assert "tpu_custom_call" in fwd.lower(x, kv, kv, *sel).compile().as_text()
    compiled = bwd.lower(x, kv, kv, x, lse, x, *sel).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert [o.shape for o in compiled.out_info] == [(bh, s, d)] * 3


# with the operands as the projections leave them (PR 45): (B, S, H * D) q,
# out, do and (B, SK, KV * D) k, v, a block one head's 128 columns; the
# three cells with heads of 128, by (batch, query heads, key-value heads)
BSHD = [
    ("laguna.win", 2, 64, 8, 8192, attn.WindowMask(512), True),
    ("laguna.full", 2, 48, 8, 8192, None, True),
    ("sdar30b", 2, 32, 4, 8192, attn.BlockDiffusionMask(4096, 4), False),
    ("keye30b", 1, 32, 4, 16384, attn.SelectedKeysMask(), True),
]


@pytest.mark.parametrize("cell,b,h,kv,s,rule,causal", BSHD,
                         ids=[c[0] for c in BSHD])
def test_mosaic_takes_the_projections_layout_at_the_cells_shapes(
        one_chip, cell, b, h, kv, s, rule, causal, d=128):
    """Both passes compile with ``(1, block, 128)`` blocks of (B, S, H *
    128) arrays under every mask rule, under the names the benchmark's
    ``kernel.flash_*`` find them by, and give out, dq, dk, dv back in that
    layout (dk, dv one a query head)."""
    shape = lambda *a: jax.ShapeDtypeStruct(*a, sharding=one_chip)  # noqa: E731
    x, kx = shape((b, s, h * d), jnp.bfloat16), \
        shape((b, s, kv * d), jnp.bfloat16)
    lse = shape((b * h, s), jnp.float32)
    sel = ()
    if isinstance(rule, attn.SelectedKeysMask):
        words = shape((b, -(-s // attn.SEL_GROUP), s, 128), jnp.int32)
        sel = (attn.Selection(words, words, shape((b, s // 128, s // 128),
                                                  jnp.bool_)),)
    kw = dict(scale=d ** -0.5, causal=causal, interpret=False, mask=rule,
              heads=h)
    fwd = jax.jit(lambda q, k, v, *sel: attn._flash_fwd_pallas(
        q, k, v, block_q=None, block_k=None, selection=(sel or (None,))[0],
        **kw))
    bwd = jax.jit(lambda q, k, v, o, lse, do, *sel: attn._flash_bwd_pallas(
        q, k, v, o, lse, do, selection=(sel or (None,))[0], **kw))
    compiled = (fwd.lower(x, kx, kx, *sel).compile(),
                bwd.lower(x, kx, kx, x, lse, x, *sel).compile())
    names = ("", "flash_bwd") if rule is None else (
        attn._kernel_name(rule, "fwd"), attn._kernel_name(rule, "bwd"))
    for done, name in zip(compiled, names):
        calls = [ln.split("=")[0] for ln in done.as_text().splitlines()
                 if "tpu_custom_call" in ln and "custom-call(" in ln]
        assert calls and all(name in c for c in calls), calls
    assert [o.shape for o in compiled[0].out_info] == [x.shape, lse.shape]
    assert [o.shape for o in compiled[1].out_info] == [x.shape] * 3
