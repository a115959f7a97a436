"""The Mamba-2 one-token state update as ONE launch a layer.

``ops/mamba2.ssm_update`` is the recurrence for one token of every slot:

    S' = exp(dt A) S + (dt x) (outer) B,      y = S' C

The launch reads each slot's state once and writes it once, in place
(the state pool is aliased to the output), and reduces ``y`` from the
block it has just computed; the XLA composition reads the state twice.

Layout. The state pool is ``[Lm, slots, N, R]`` with ``R = heads x head
size`` on the lanes and the state size ``N`` on the sublanes, so that
everything which varies by (head, row) is a lane vector ``[1, R]`` and
``B`` and ``C`` are sublane vectors ``[N, 1]``: every broadcast and the
reduction over ``N`` are the cheap kind. ``layer`` (scalar prefetch)
picks the layer of the pool, so that no slice of the pool is made.

With ``G`` B/C groups (head ``h`` reads group ``h // (H / G)``) a
group owns ``R / G`` consecutive lanes. A lane block keeps the size it
has at ``G = 1`` (a block of 512 lanes moves 0.5 MB a grid step, of
2048 lanes 2 MB: the step's fixed cost is the same) and so holds a
whole number of groups, or lies inside one: the kernel walks the
block's groups in a static loop, each on its own aligned lane slice
with that group's ``B`` and ``C`` columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import audited_pallas_call, interpret_mode, no_x64

F32 = jnp.float32


def _kernel(layer_ref, decay_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref,
            o_ref):
    del layer_ref
    new = (s_ref[...].astype(F32) * decay_ref[...]
           + b_ref[...] * xdt_ref[...])                 # [N, rb]
    stored = new.astype(o_ref.dtype)
    o_ref[...] = stored
    # the stored value is what later steps read: read it here too
    y_ref[...] = jnp.sum(stored.astype(F32) * c_ref[...], axis=0,
                         keepdims=True)


def _kernel_groups(layer_ref, decay_ref, xdt_ref, b_ref, c_ref, s_ref,
                   y_ref, o_ref):
    """The same update on a block that holds ``b_ref.shape[0]`` groups
    side by side: b_ref / c_ref [groups, N, 1], the others [.., rb]."""
    del layer_ref
    groups = b_ref.shape[0]
    gl = s_ref.shape[-1] // groups
    for g in range(groups):
        at = (slice(None), pl.ds(g * gl, gl))
        new = (s_ref[at].astype(F32) * decay_ref[at]
               + b_ref[g] * xdt_ref[at])                # [N, gl]
        stored = new.astype(o_ref.dtype)
        o_ref[at] = stored
        y_ref[at] = jnp.sum(stored.astype(F32) * c_ref[g], axis=0,
                            keepdims=True)


def group_blocks(rows: int, groups: int):
    """(lanes of a block, groups a block holds, blocks a group spans)
    for ``groups`` B/C groups over ``rows`` lanes, or None where a
    group's lanes are no whole number of 128 (no aligned lane slice:
    the caller computes the composition)."""
    gl = rows // groups
    if rows % groups or gl % 128:
        return None
    rb = lane_block(rows)
    if rb % gl and gl % rb:
        rb = lane_block(gl)
    return rb, max(1, rb // gl), max(1, gl // rb)


def lane_block(rows: int, cap: int = 2048) -> int:
    """The lanes of one block: the largest multiple of 128 that divides
    ``rows`` and is at most ``cap`` (``rows`` itself when it is small or
    has no such divisor)."""
    if rows <= cap or rows % 128:
        return rows
    rb = cap - cap % 128
    while rows % rb:
        rb -= 128
    return rb


@no_x64
def ssm_update_pallas(decay, xdt, b, c, pool, layer):
    """decay, xdt: [S, R] float32 (``exp(dt A)`` and ``dt x`` spread
    over a head's rows); b, c: [S, N] float32, or [S, G, N] for ``G``
    B/C groups (``group_blocks(R, G)`` must not be None); pool: [Lm, S,
    N, R] in its storage type. Returns (y [S, R] float32, the pool with
    layer ``layer``'s states replaced)."""
    Lm, S, N, R = pool.shape
    if b.ndim == 3 and b.shape[1] == 1:
        b, c = b[:, 0], c[:, 0]
    if b.ndim == 2:
        kernel, rb = _kernel, lane_block(R)
        col = pl.BlockSpec((None, N, 1), lambda s, j, l: (s, 0, 0))
        b, c = (t.astype(F32)[:, :, None] for t in (b, c))
    else:
        kernel = _kernel_groups
        rb, held, span = group_blocks(R, b.shape[1])
        # block j's groups: the j-th ``held`` of them, or (a group
        # spanning ``span`` blocks) group j // span
        col = pl.BlockSpec((None, held, N, 1),
                           lambda s, j, l: (s, j // span, 0, 0))
        b, c = (t.astype(F32)[..., None] for t in (b, c))
    row = pl.BlockSpec((None, 1, rb), lambda s, j, l: (s, 0, j))
    state = pl.BlockSpec((None, None, N, rb),
                         lambda s, j, l: (l[0], s, 0, j))
    y, pool = audited_pallas_call(
        kernel, name="ssm_update", num_scalar_prefetch=1,
        grid=(S, R // rb),
        in_specs=[row, row, col, col, state],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((S, 1, R), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (after the prefetched layer) is the pool: in place
        input_output_aliases={5: 1},
        interpret=interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      decay.astype(F32)[:, None, :], xdt.astype(F32)[:, None, :], b, c,
      pool)
    return y[:, 0, :], pool


# -- one slot's state, for the prefill chunk ---------------------------
# A chunk reads its slot's state (4 MB a layer at the published widths)
# and writes it back. As a launch each way the pool keeps its layout:
# handed a dynamic-slice and a dynamic-update-slice the compiler re-lays
# out the WHOLE pool (2.4 GB, copied in and out of every chunk) to suit
# the products that consume and produce those 4 MB.
def _copy_kernel(idx_ref, src_ref, dst_ref):
    del idx_ref
    dst_ref[...] = src_ref[...]


def _write_kernel(idx_ref, src_ref, pool_ref, dst_ref):
    del idx_ref, pool_ref
    dst_ref[...] = src_ref[...].astype(dst_ref.dtype)


def _slot_specs(N, rb):
    at_slot = pl.BlockSpec((None, None, N, rb),
                           lambda j, i: (i[0], i[1], 0, j))
    return at_slot, pl.BlockSpec((N, rb), lambda j, i: (0, j))


@no_x64
def slot_state_read(pool, layer, slot):
    """pool [Lm, S, N, R] -> the state [N, R] of ``slot`` at ``layer``."""
    _, _, N, R = pool.shape
    rb = lane_block(R)
    at_slot, block = _slot_specs(N, rb)
    idx = jnp.stack([jnp.asarray(layer, jnp.int32),
                     jnp.asarray(slot, jnp.int32)])
    return audited_pallas_call(
        _copy_kernel, name="ssm_state_read", num_scalar_prefetch=1,
        grid=(R // rb,), in_specs=[at_slot], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((N, R), pool.dtype),
        interpret=interpret_mode())(idx, pool)


@no_x64
def slot_state_write(pool, layer, slot, state):
    """The pool with ``slot``'s state at ``layer`` replaced by ``state``
    [N, R], in place (the pool is aliased to the output)."""
    _, _, N, R = pool.shape
    rb = lane_block(R)
    at_slot, block = _slot_specs(N, rb)
    idx = jnp.stack([jnp.asarray(layer, jnp.int32),
                     jnp.asarray(slot, jnp.int32)])
    return audited_pallas_call(
        _write_kernel, name="ssm_state_write", num_scalar_prefetch=1,
        grid=(R // rb,), in_specs=[block, at_slot], out_specs=at_slot,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        interpret=interpret_mode())(idx, state, pool)
