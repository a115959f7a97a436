"""Mamba-2 (state-space duality) mixer pieces: the chunked scan that a
prefill chunk runs, the one-token state update that a decode step runs,
and the short causal convolution in front of both.

Per head, with ``a_t = dt_t * A`` (``A < 0``), state ``S`` of shape
[head size, state size]:

    S_t = exp(a_t) S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D x_t

``ssd_scan`` computes a whole chunk of positions from the state the
previous chunk left, blocked as the paper's SSD algorithm: inside a
block of ``block`` positions the quadratic (attention-like) form, across
blocks the recurrence on one state a block. ``ssm_update`` is the
recurrence itself for one token of every slot. A position with
``dt == 0`` leaves the state exactly as it was (``exp(0) * S + 0``), so
padding and idle slots are masked by their ``dt`` alone.

Everything that touches the state is float32 at ``Precision.HIGHEST``:
on the chip a float32 contraction otherwise rounds its operands to
bfloat16, and the state would be read at that precision whatever type
it is stored in. The contractions are small beside the projections.
The state is laid out ``[N, H*hp]`` (the state size first, a head's
rows last: ``ops/pallas/mamba2.py`` says why) wherever it is stored or
handed over. The computations sit in ``jax.named_scope``s (``ssd_scan``,
``ssm_update``: names of ``observability.PROGRAM_SCOPES``), which a
compiled program's text shows. A device trace's event names do not
carry them (PR 27, on the v5e); a trace's reader gets an operation's
scope from the registry of compiled programs
(``observability/programs.py``: the engine notes each program at its
first dispatch under a profiler session, ``engine.program_scopes()``
gives {instruction name: scope}), not from the event's name. By name it finds a Pallas launch:
``ssm_update``, ``ssm_state_read``, ``ssm_state_write``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d", "conv_update", "ssd_scan", "ssm_update",
           "split_xbc", "slot_state", "set_slot_state"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def split_xbc(xbc, heads, head_dim, groups, state):
    """[..., heads*head_dim + 2*groups*state] -> x [..., heads,
    head_dim], B and C [..., groups, state]."""
    d_in = heads * head_dim
    lead = xbc.shape[:-1]
    x = xbc[..., :d_in].reshape(*lead, heads, head_dim)
    b = xbc[..., d_in:d_in + groups * state].reshape(*lead, groups, state)
    c = xbc[..., d_in + groups * state:].reshape(*lead, groups, state)
    return x, b, c


def causal_conv1d(x, weight, bias, tail, n_valid):
    """Depthwise causal convolution over one chunk, then SiLU.

    x: [P, C] the chunk's inputs (rows at and after ``n_valid`` are
    padding); weight: [K, C] (tap ``k`` multiplies the input ``K-1-k``
    positions back); bias: [C]; tail: [K-1, C] the inputs of the K-1
    positions before the chunk, oldest first (zeros at a sequence's
    start). Returns (y [P, C], the tail after the last real position).
    """
    K = weight.shape[0]
    P = x.shape[0]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=0)  # [P+K-1, C]
    acc = bias.astype(F32)[None, :]
    for k in range(K):
        acc = acc + ext[k:k + P].astype(F32) * weight[k].astype(F32)[None]
    # ext row i is chunk row i - (K-1): the last K-1 real rows are
    # ext[n_valid : n_valid + K - 1] (with n_valid == 0 the old tail)
    new_tail = jax.lax.dynamic_slice_in_dim(
        ext, jnp.asarray(n_valid, jnp.int32), K - 1, axis=0)
    return jax.nn.silu(acc).astype(x.dtype), new_tail.astype(tail.dtype)


def conv_update(x, weight, bias, tail, active):
    """One position of the same convolution for every slot.

    x: [S, C]; tail: [S, K-1, C]; active: [S] bool — an idle slot's tail
    stays as it is. Returns (y [S, C], new tail)."""
    K = weight.shape[0]
    acc = bias.astype(F32)[None, :] + x.astype(F32) * weight[K - 1].astype(F32)
    for k in range(K - 1):
        acc = acc + tail[:, k].astype(F32) * weight[k].astype(F32)[None]
    shifted = jnp.concatenate(
        [tail[:, 1:], x[:, None, :].astype(tail.dtype)], axis=1)
    new_tail = jnp.where(active[:, None, None], shifted, tail)
    return jax.nn.silu(acc).astype(x.dtype), new_tail


def ssd_scan(x, dt, a, b, c, d, state, block=256):
    """The chunked scan over P positions of one sequence.

    x: [P, H, hp]; dt: [P, H] float32, after softplus, 0 at padding;
    a: [H] float32 (negative); b, c: [P, G, N] (heads share a group's B
    and C in order: head h uses group h // (H // G)); d: [H];
    state: [N, H*hp] (the state pool's form: the state size first, a
    head's rows last), what the previous chunk left.
    Returns (y [P, H, hp] float32, state [N, H*hp] float32).
    ``block`` only sets how the positions are blocked, not the result.
    """
    P, H, hp = x.shape
    G, N = b.shape[1], b.shape[2]
    Q = min(block, P)
    if P % Q:
        raise ValueError(f"ssd_scan: {P} positions do not divide into "
                         f"blocks of {Q}")
    nc, rep = P // Q, H // G
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    af = a.astype(F32).reshape(G, rep)
    df = d.astype(F32).reshape(G, rep)

    def one_block(s, xs):
        """Positions i, j of one block; s [N, G, rep, hp] before it."""
        xf, dtf, bf, cf = xs
        la = jnp.cumsum(dtf * af, axis=0)                   # [Q, G, rep]
        xdt = xf * dtf[..., None]
        # inside: y_i += sum_{j<=i} exp(la_i - la_j) (C_i.B_j) dt_j x_j
        cb = jnp.einsum("ign,jgn->gij", cf, bf, precision=HIGHEST)
        decay = jnp.where(causal[:, :, None, None], jnp.exp(jnp.minimum(
            la[:, None] - la[None, :], 0.0)), 0.0)          # [i, j, G, rep]
        y = jnp.einsum("gij,ijgr,jgrp->igrp", cb, decay, xdt,
                       precision=HIGHEST)
        # from before: the state as the block found it, decayed to i
        y = y + jnp.einsum("ign,ngrp->igrp", cf, s,
                           precision=HIGHEST) * jnp.exp(la)[..., None]
        y = y + xf * df[..., None]
        # what the block leaves: the old state decayed over all of it,
        # and each position's outer product decayed to the block's end
        add = jnp.einsum("jgr,jgn,jgrp->ngrp", jnp.exp(la[-1:] - la), bf,
                         xdt, precision=HIGHEST)
        return s * jnp.exp(la[-1])[None, ..., None] + add, y

    with jax.named_scope("ssd_scan"):
        s0 = state.astype(F32).reshape(N, G, rep, hp)
        # one loop body whatever the number of blocks, so a chunk's
        # padding blocks change nothing of what the real ones compute
        s_end, y = jax.lax.scan(one_block, s0, (
            x.astype(F32).reshape(nc, Q, G, rep, hp),
            dt.astype(F32).reshape(nc, Q, G, rep),
            b.astype(F32).reshape(nc, Q, G, N),
            c.astype(F32).reshape(nc, Q, G, N)))
        return y.reshape(P, H, hp), s_end.reshape(N, H * hp)


def slot_state(pool, layer, slot):
    """One slot's state [N, H*hp] at ``layer`` of the pool [Lm, S, N,
    H*hp] (a prefill chunk's read). On the chip a launch, so that the
    pool keeps its layout (``ops/pallas/mamba2.py`` says why)."""
    from .pallas._util import pallas_route
    if pallas_route():
        from .pallas.mamba2 import slot_state_read
        return slot_state_read(pool, layer, slot)
    return pool[layer, slot]


def set_slot_state(pool, layer, slot, state):
    """The pool with that slot's state replaced, in its storage type."""
    from .pallas._util import pallas_route
    if pallas_route():
        from .pallas.mamba2 import slot_state_write
        return slot_state_write(pool, layer, slot, state)
    return pool.at[layer, slot].set(state.astype(pool.dtype))


def ssm_update(x, dt, a, b, c, d, pool, layer):
    """One token of every slot: the recurrence itself, on layer
    ``layer`` of the state pool.

    x: [S, H, hp]; dt: [S, H] float32 after softplus, 0 for a slot that
    is not decoding (its state then stays bit for bit); a, d: [H];
    b, c: [S, G, N]; pool: [Lm, S, N, H*hp] in its storage type (the
    state size on the sublanes, a head's rows on the lanes: see
    ``ops/pallas/mamba2.py``). Returns (y [S, H, hp] float32, pool).
    On the chip one Pallas launch a layer (``ssm_update``), which
    reads and writes each state once, whatever the number of B/C
    groups whose lanes are whole tiles; elsewhere the composition
    below."""
    from .pallas._util import pallas_route
    from .pallas.mamba2 import group_blocks, ssm_update_pallas
    S, H, hp = x.shape
    G = b.shape[1]
    with jax.named_scope("ssm_update"):
        dtf = dt.astype(F32)
        decay = jnp.repeat(jnp.exp(dtf * a.astype(F32)[None]), hp, axis=1)
        xdt = (x.astype(F32) * dtf[..., None]).reshape(S, H * hp)
        if pallas_route() and (G == 1 or group_blocks(H * hp, G)):
            y, pool = ssm_update_pallas(decay, xdt, b, c, pool, layer)
        else:
            rows = (H // G) * hp                 # rows that share a group
            bn = jnp.repeat(b.astype(F32), rows, axis=1).transpose(0, 2, 1)
            cn = jnp.repeat(c.astype(F32), rows, axis=1).transpose(0, 2, 1)
            old = jax.lax.dynamic_index_in_dim(pool, layer, 0, False)
            new = (old.astype(F32) * decay[:, None, :]
                   + bn * xdt[:, None, :]).astype(pool.dtype)   # [S, N, R]
            # the stored value is what later steps read: read it here too
            y = jnp.sum(new.astype(F32) * cn, axis=1)
            pool = jax.lax.dynamic_update_index_in_dim(pool, new, layer, 0)
        y = y.reshape(S, H, hp) + x.astype(F32) * d.astype(F32)[None, :, None]
        return y, pool
