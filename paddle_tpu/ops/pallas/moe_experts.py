"""The held experts' grouped product as a launch that visits the
TOUCHED experts only, in blocks of rows.

``jax.lax.ragged_dot`` is XLA's own grouped product. On the chip it
tiles every matrix dimension by the largest power of two (up to 512)
that divides it: 512 x 512 at granite's widths (4096, 1536), but 128 x
128 where a dimension is an odd multiple of 128 (2688 = 21 x 128, 1920 =
15 x 128): 32 KB of weights a grid step, and a decode step's 64 experts
take 6.2 ms a product where their bytes take 0.8. This launch moves a
whole ``[K, tn]`` column tile of one expert a step (3.4 MB at K = 2688):

- the assignments are laid out BY EXPERT in blocks of ``TM`` rows, each
  expert's group padded up to whole blocks (``layout``), so that a block
  belongs to one expert;
- the grid is (column tiles, row blocks), the row blocks innermost:
  consecutive blocks of one expert find its tile where it is, an expert
  nobody chose has no block and is never fetched, and the blocks past
  the last used one do nothing (and fetch nothing: they name the last
  used expert);
- ``layer`` (scalar prefetch) picks the layer of the stack, so that no
  slice of the stack is made.

The first product applies the expert's activation to its result
(``relu(h)^2``); the second is plain. A gated expert (``silu(g) * v``
over two column halves) is not built here: its families' widths tile
well under ``ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import audited_pallas_call, interpret_mode, no_x64
from .mamba2 import lane_block

F32 = jnp.float32
TM = 16                  # rows of a block: a bfloat16 tile's sublanes


def xla_tile(n: int) -> int:
    """The tile XLA's ``ragged_dot`` gives a dimension of ``n``: the
    largest of 512, 256, 128 that divides it, 128 where none does
    (``tests/test_chip_compile.py`` reads it out of the compiled text
    at the benchmark's widths: the rule is XLA's, not JAX's to keep)."""
    t = 512
    while t > 128 and n % t:
        t //= 2
    return t


def supports(K: int, N: int, F: int, act: str, dtype) -> tuple:
    """(whether this launch takes an expert layer of ``w_in`` [.., K,
    N] and ``w_out`` [.., F, K], why). It takes the layers XLA would
    tile 128 x 128."""
    if act != "relu2":
        return False, f"activation {act!r}: a gated expert is not built"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return False, f"{jnp.dtype(dtype)} weights: blocks of {TM} rows " \
                      "are bfloat16's"
    if K % 128 or N % 128 or F % TM:
        return False, f"widths {K}, {N}, {F} are no whole tiles"
    if max(xla_tile(K), xla_tile(N)) > 128:
        return False, (f"ragged_dot tiles {K} x {N} by {xla_tile(K)} x "
                       f"{xla_tile(N)}: XLA's launch is taken")
    return True, f"ragged_dot would tile {K} x {N} by 128 x 128"


def blocks_for(assignments: int, held: int) -> int:
    """Row blocks that hold ``assignments`` rows over ``held`` experts
    whatever their distribution: each group padded up to whole blocks."""
    return -(-(assignments + held * (TM - 1)) // TM)


def layout(key, held: int):
    """Where each assignment's row lies when the rows are laid out by
    expert in blocks of ``TM``.

    key [A] int32: the held expert (0 .. held-1) of each assignment,
    ``held`` for one that goes to an expert held elsewhere. Returns
    (dest [A]: its row among the ``blocks_for(A, held) * TM`` padded
    rows, one past the last for an assignment held elsewhere; src
    [rows]: the assignment that feeds each padded row (0 where none);
    block_expert [blocks] int32; n_used [1] int32: blocks in use)."""
    A = key.shape[0]
    nb = blocks_for(A, held)
    i32 = jnp.int32
    oh = jax.nn.one_hot(key, held, dtype=i32)                # [A, held]
    rank = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=1)
    blocks = (jnp.sum(oh, axis=0) + (TM - 1)) // TM          # a expert
    ends = jnp.cumsum(blocks)
    first = ends - blocks
    here = key < held
    dest = jnp.where(
        here, jnp.take(first, jnp.minimum(key, held - 1)) * TM + rank,
        nb * TM).astype(i32)
    n_used = ends[-1]
    # block b is of the first expert whose blocks end past it; the
    # blocks past the last used one name that one's expert
    b = jnp.minimum(jnp.arange(nb, dtype=i32), jnp.maximum(n_used - 1, 0))
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, b, side="right"), held - 1).astype(i32)
    src = jnp.zeros((nb * TM + 1,), i32).at[dest].set(
        jnp.arange(A, dtype=i32))[:-1]
    return dest, src, block_expert, n_used.astype(i32).reshape(1)


def _kernel(layer_ref, expert_ref, used_ref, x_ref, w_ref, o_ref, *, act,
            k):
    del layer_ref, expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        h = jnp.dot(x_ref[:, :k], w_ref[...], preferred_element_type=F32)
        if act == "relu2":
            h = jnp.square(jnp.maximum(h, 0.0))
        o_ref[...] = h.astype(o_ref.dtype)


@no_x64
def grouped_product(x, w, layer, block_expert, n_used, act=None,
                    out_dtype=None):
    """x [rows, K'] laid out by :func:`layout` (K' >= K: columns past K
    are not read) times the experts' matrices w [L, held, K, N] (or
    [held, K, N]) at ``layer``: block ``b`` of ``TM`` rows by expert
    ``block_expert[b]``'s, the first ``n_used`` blocks only; ``act``
    "relu2" squares the positive part of the result. Returns [rows, N]
    (rows of unused blocks are whatever the launch left there)."""
    if w.ndim == 3:
        w, layer = w[None], 0
    _, _, K, N = w.shape
    rows = x.shape[0]
    nb, tn = rows // TM, lane_block(N, cap=1024)
    i32 = jnp.int32
    return audited_pallas_call(
        functools.partial(_kernel, act=act, k=K), name="moe_grouped",
        num_scalar_prefetch=3, grid=(N // tn, nb),
        in_specs=[
            pl.BlockSpec((TM, x.shape[1]), lambda j, b, l, e, u: (b, 0)),
            pl.BlockSpec((None, None, K, tn),
                         lambda j, b, l, e, u: (l[0], e[b], 0, j))],
        out_specs=pl.BlockSpec((TM, tn), lambda j, b, l, e, u: (b, j)),
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype or x.dtype),
        interpret=interpret_mode(),
    )(jnp.asarray(layer, i32).reshape(1), block_expert.astype(i32),
      n_used.astype(i32), x, w)
