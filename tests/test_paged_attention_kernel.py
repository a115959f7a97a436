"""The paged-attention decode kernel (interpret mode): a grid over slots,
a loop over each slot's own blocks of ``P`` live pages, the pools left
in HBM and fetched by the kernel itself, one online-softmax update a
block.

The reference is the XLA gather composition,
``paged_attention_decode_xla``, to a stated tolerance (``_agrees``):
float32 pools to 16 float32 ulps of the output, bfloat16 pools to one
bfloat16 ulp. A second reference, to the same tolerance, is the launch
this kernel's first form replaced, kept here: a grid of (slots, pages
of the table) that visits every page of ``max_seq_len`` and sends each
live one, in page order, through ``online_softmax_page_update`` (a
softmax update a page). The kernel's own bits are compared only with
the kernel's own: a block's reduction order follows ``P``."""
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu  # noqa: F401 — x64 mode, as every kernel caller has it
from paddle_tpu.ops.paged_attention import paged_attention_decode_xla
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas._util import (no_x64,
                                         online_softmax_page_update)

BS, P, MB = 8, 2, 5


def _table_grid_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                       l_scr, acc_scr, *, scale, bs, kv, groups):
    b, pg = pl.program_id(0), pl.program_id(1)
    seq_len = len_ref[b]

    @pl.when(pg == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(pg * jnp.int32(bs) < seq_len)
    def _page():
        online_softmax_page_update(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), pg, bs, seq_len, scale, kv,
            groups, m_scr, l_scr, acc_scr)

    @pl.when(pg == pl.num_programs(1) - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == jnp.float32(0.0), jnp.float32(1.0), l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _clamped_page(bs):
    """The reference grid's page fetch: dead pages clamp to the slot's
    last live page (garbage table entries stay out of the fetch)."""
    def f(b, pg, bt_ref, len_ref):
        last = jnp.maximum(len_ref[b] - jnp.int32(1),
                           jnp.int32(0)) // jnp.int32(bs)
        return (bt_ref[b, jnp.minimum(pg.astype(jnp.int32), last)],
                0, 0, 0)
    return f


@no_x64
def _page_by_page(q, k_pool, v_pool, bt, lens, scale=None):
    """The replaced launch: one grid step a page of the table, live or
    not, one page in flight."""
    B, H, hd = q.shape
    bs, KV = k_pool.shape[-3:-1]
    mb = bt.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    page = pl.BlockSpec((1, bs, KV, hd), _clamped_page(bs))
    row = pl.BlockSpec((1, H, hd), lambda b, pg, *_: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_table_grid_kernel, scale=scale, bs=bs, kv=KV,
                          groups=H // KV),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, mb),
            in_specs=[row, page, page], out_specs=row,
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=True,
    )(jnp.asarray(bt, jnp.int32), jnp.asarray(lens, jnp.int32), q, k_pool,
      v_pool)
    return np.asarray(out, np.float32)


def _case(seed, B, H, KV, hd, N, dtype=jnp.float32, L=None, bs=BS, mb=MB):
    rng = np.random.RandomState(seed)
    lead = (L,) if L else ()
    q = jnp.asarray(rng.randn(B, H, hd), dtype)
    kp = jnp.asarray(rng.randn(*lead, N, bs, KV, hd), dtype)
    vp = jnp.asarray(rng.randn(*lead, N, bs, KV, hd), dtype)
    bt = jnp.asarray(np.stack([rng.permutation(N)[:mb] for _ in range(B)]),
                     jnp.int32)
    return q, kp, vp, bt


def _f32(x):
    return np.asarray(x, np.float32)


def _agrees(got, want, dtype=jnp.float32, ulps=None):
    """``got`` within ``ulps`` units in the last place of ``want``, as
    ``dtype`` holds the output: 16 for float32 (a score of size 4 is
    a float32 sum that two orders of summation give a few ulps apart,
    and ``exp`` turns a score's absolute error into a weight's relative
    one), 1 for bfloat16 (both sides round such sums once).
    An element is a weighted mean of signed values, so the unit is the
    ulp of its head's largest element (never under 2**-6): what
    cancels in a small element still carries the sum's error."""
    got, want = _f32(got), _f32(want)
    ulps = ulps or (1 if dtype == jnp.bfloat16 else 16)
    mag = np.maximum(np.abs(want).max(axis=-1, keepdims=True), 2.0 ** -6)
    mag = np.broadcast_to(mag, want.shape)
    ulp = float(jnp.finfo(dtype).eps) * 2.0 ** np.floor(np.log2(mag))
    err = np.abs(got - want)
    worst = np.unravel_index(np.argmax(err / ulp), err.shape)
    assert (err <= ulps * ulp).all(), (
        f"{err[worst] / ulp[worst]:.2f} ulps at {worst}: "
        f"{got[worst]} against {want[worst]}")


@pytest.mark.parametrize("length", [0, 1, BS - 1, BS, P * BS - 1, P * BS,
                                    P * BS + 1, MB * BS, MB * BS + 3])
def test_every_boundary_length(length):
    """A slot of each length a block boundary makes special, beside a
    slot that is full and one that is empty: the attention of the XLA
    composition and of the page-by-page loop, zeros for an empty slot.
    A length past the table (no caller sends one) stops at the table's
    last page, as the replaced grid did."""
    q, kp, vp, bt = _case(length, 3, 4, 2, 16, 24)
    lens = jnp.asarray([length, MB * BS, 0], jnp.int32)
    got = _f32(pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                                pages_per_step=P))
    _agrees(got, paged_attention_decode_xla(q, kp, vp, bt, lens))
    _agrees(got, _page_by_page(q, kp, vp, bt, lens))
    assert not got[2].any() and (length or not got[0].any())


@pytest.mark.parametrize("garbage", ["nan_page", "out_of_range"])
def test_table_entries_past_a_length_are_never_used(garbage):
    """Past a slot's last live page its table row holds whatever the
    last owner left: an entry there is neither read nor fetched, so a
    page of NaNs it names, or a page number past the pool, changes
    nothing."""
    N = 24
    q, kp, vp, bt = _case(3, 3, 4, 2, 16, N)
    lens = np.asarray([BS + 3, 1, 0], np.int32)
    clean = pa.paged_attention_decode_pallas(q, kp, vp, bt,
                                             jnp.asarray(lens),
                                             pages_per_step=P)
    bad = np.asarray(bt).copy()
    live = -(-lens // BS)
    if garbage == "nan_page":
        dead = [p for p in range(N)
                if p not in {int(bad[b, j]) for b in range(3)
                             for j in range(live[b])}][0]
        kp = kp.at[dead].set(jnp.nan)
        vp = vp.at[dead].set(jnp.nan)
        fill = dead
    else:
        fill = 2 ** 30
    for b in range(3):
        bad[b, live[b]:] = fill
    got = pa.paged_attention_decode_pallas(q, kp, vp, jnp.asarray(bad),
                                           jnp.asarray(lens),
                                           pages_per_step=P)
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(clean))


@pytest.mark.parametrize("pages", [1, 2, 3, *pa.PAGE_BLOCK_CANDIDATES])
def test_every_block_size_agrees_with_the_reference(pages):
    """``pages_per_step`` sets how many pages one softmax update
    reduces, so it moves the last float32 places and nothing more (a
    block larger than the table is cut to it)."""
    mb = 19
    q, kp, vp, bt = _case(11, 4, 8, 2, 16, 40, mb=mb)
    lens = jnp.asarray([0, 5, 8 * BS + 1, mb * BS], jnp.int32)
    got = pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                           pages_per_step=pages)
    _agrees(got, paged_attention_decode_xla(q, kp, vp, bt, lens))
    _agrees(got, _page_by_page(q, kp, vp, bt, lens))


@pytest.mark.parametrize("layer", ["int", "traced"])
def test_stacked_pools_read_at_their_layer(layer):
    """The pools of every layer in one buffer and the layer an operand
    (a Python int, or traced inside a loop over layers): the bits of
    the same launch over that layer's slice, the attention of the
    reference over it."""
    L = 3
    q, kp, vp, bt = _case(5, 2, 4, 2, 16, 20, L=L)
    lens = jnp.asarray([P * BS + 3, 6], jnp.int32)
    want = np.stack([_f32(pa.paged_attention_decode_pallas(
        q, kp[i], vp[i], bt, lens, pages_per_step=P)) for i in range(L)])
    if layer == "int":
        got = jnp.stack([pa.paged_attention_decode_pallas(
            q, kp, vp, bt, lens, pages_per_step=P, layer=i)
            for i in range(L)])
    else:
        got = jax.jit(lambda *a: jax.lax.map(
            lambda i: pa.paged_attention_decode_pallas(
                *a, pages_per_step=P, layer=i),
            jnp.arange(L, dtype=jnp.int32)))(q, kp, vp, bt, lens)
    np.testing.assert_array_equal(_f32(got), want)
    _agrees(got, np.stack([_f32(paged_attention_decode_xla(
        q, kp[i], vp[i], bt, lens)) for i in range(L)]))


@pytest.mark.parametrize("H,KV,scale", [
    (8, 2, None),          # a four-chip shard of Mistral's 32/8 heads
    (32, 8, None),         # Mistral-7B, one chip
    (32, 8, 1.0 / 128),    # the granite attention layer's multiplier
])
def test_head_layouts_and_scale_at_real_head_size(H, KV, scale):
    """hd=128, BS=16, bfloat16 pools, as the cells run it; the block
    size resolved from the shapes (no ``pages_per_step``)."""
    bs, mb = 16, 12
    q, kp, vp, bt = _case(H, 2, H, KV, 128, 30, jnp.bfloat16, bs=bs, mb=mb)
    lens = jnp.asarray([9 * bs + 5, 17], jnp.int32)
    got = _f32(pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                                scale=scale))
    _agrees(got, paged_attention_decode_xla(q, kp, vp, bt, lens,
                                            scale=scale), jnp.bfloat16)
    _agrees(got, _page_by_page(q, kp, vp, bt, lens, scale=scale),
            jnp.bfloat16)


REAL = {"mistral_one_chip": (4, 32, 8, None),
        "mistral_tp4_shard": (4, 8, 2, None),
        "granite_layer": (64, 32, 8, 1.0 / 128)}


@pytest.mark.parametrize("layout", list(REAL))
def test_real_head_layouts_against_the_reference(layout):
    """The three launches the benchmark's cells make (hd=128, BS=16,
    bfloat16, the block of pages the shapes resolve to), slots of
    every kind in one grid: empty, one token, inside the first block,
    a block exactly, a block and a partial page, several blocks."""
    B, H, KV, scale = REAL[layout]
    bs, mb, pp = 16, 40, pa.PAGE_BLOCK_CANDIDATES[0]
    assert pa.page_block_candidates(bs, KV, 128, mb, "bfloat16")[0] == pp
    q, kp, vp, bt = _case(B, B, H, KV, 128, 2 * mb, jnp.bfloat16, bs=bs,
                          mb=mb)
    kinds = [0, 1, 3 * bs + 7, pp * bs, pp * bs + 3, 2 * pp * bs + bs + 9]
    lens = jnp.asarray([kinds[b % len(kinds)] for b in range(B)],
                       jnp.int32)
    got = pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                           scale=scale)
    _agrees(got, paged_attention_decode_xla(q, kp, vp, bt, lens,
                                            scale=scale), jnp.bfloat16)
    assert not _f32(got)[0].any()


@pytest.mark.parametrize("edge", ["block-1", "block", "block+1",
                                  "2*block-page+1"])
@pytest.mark.parametrize("pages", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lengths_around_a_block_edge(edge, pages, dtype):
    """Pages of 16 in blocks of 8 and of 16 (what the cells run): one
    token short of a block, a block exactly, one token into the second
    block, one token into the second block's last page. The partial
    block is one update masked by position."""
    dtype = jnp.dtype(dtype).type
    block = pages * 16
    length = {"block-1": block - 1, "block": block, "block+1": block + 1,
              "2*block-page+1": 2 * block - 16 + 1}[edge]
    q, kp, vp, bt = _case(length, 2, 8, 2, 32, 48, dtype, bs=16,
                          mb=2 * pages + 1)
    lens = jnp.asarray([length, 5], jnp.int32)
    got = pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                           pages_per_step=pages)
    _agrees(got, paged_attention_decode_xla(q, kp, vp, bt, lens), dtype)


@pytest.mark.parametrize("stale", ["previous_slot", "earlier_block"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_stale_buffer_contributes_nothing(stale, dtype):
    """A partial block leaves in VMEM what an earlier block or slot
    fetched there. Slot 0 holds a page of NaN at block position 5 (of
    its first block, or of its second, the buffer's other half); slot 1,
    the next grid step, is shorter, so that position is dead for it:
    its output is finite and the reference's. ``0 x NaN`` is NaN, so
    this holds only where V's dead rows are selected away, not only
    their weights."""
    dtype = jnp.dtype(dtype).type
    bs, pp, j = 16, 8, 5
    q, kp, vp, _ = _case(7, 2, 8, 2, 32, 48, dtype, bs=bs, mb=20)
    bt = jnp.asarray(np.random.RandomState(7).permutation(48)[:40]
                     .reshape(2, 20), jnp.int32)   # no page shared
    first = stale == "previous_slot"
    blk = 0 if first else 1
    lens = jnp.asarray([(blk + 1) * pp * bs,
                        blk * pp * bs + 3 * bs + 5], jnp.int32)
    poisoned = int(bt[0, blk * pp + j])
    kp, vp = kp.at[poisoned].set(jnp.nan), vp.at[poisoned].set(jnp.nan)
    got = _f32(pa.paged_attention_decode_pallas(q, kp, vp, bt, lens,
                                                pages_per_step=pp))
    assert np.isnan(got[0]).all() and np.isfinite(got[1]).all()
    _agrees(got[1], paged_attention_decode_xla(q, kp, vp, bt, lens)[1],
            dtype)


@pytest.mark.parametrize("KV,MB_,want", [
    (8, 160, [16, 8, 4]),      # Mistral's cells: all fit, 16 first
    (2, 160, [16, 8, 4]),      # the four-chip shard
    (8, 6, [4]),               # a table shorter than a block
    (8, 3, [1]),               # shorter than every candidate
])
def test_block_candidates_follow_table_and_budget(KV, MB_, want,
                                                  monkeypatch):
    assert pa.page_block_candidates(16, KV, 128, MB_, "bfloat16") == want
    # a budget that admits 4 pages of 32 KiB in each of four buffers
    # (K and V, two halves), and no more
    monkeypatch.setenv("PADDLE_TPU_FUSED_VMEM_BUDGET", str(4 * 4 * 32768))
    tight = pa.page_block_candidates(16, 8, 128, 160, "bfloat16")
    assert tight == [4]
