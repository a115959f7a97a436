"""Tensor-parallel serving over a named mesh.

ROADMAP item 1, stage 1: shard the serving hot path — the paged KV
pools, the QKV/o-proj/MLP weights and the per-slot attention
computation — along the HEAD axis of a 1-D named mesh via ``shard_map``
(through the ``core/jax_compat.py`` shims), so ``ServingEngine`` /
``generate_paged`` keep running ONE jitted decode program and <=1
prefill program per bucket while N chips split the attention bandwidth
and hold N× the resident KV pages (FlashFuser's inter-core scaling
argument; ClusterFusion++'s full-block decode model — PAPERS.md).

Sharding scheme (:func:`paddle_tpu.models.llama.tp_param_specs`):

- KV pools ``[L, N_pages, BS, KV, hd]`` shard axis 3 (KV heads). The
  page TABLES stay host-global — a page index names the same physical
  page on every shard, each shard holding that page's slice of the
  head axis — so the ``BlockManager``, the radix prefix cache, COW
  forks and LRU eviction work completely unchanged.
- q/k/v/gate/up projections shard their OUTPUT columns (head-major, so
  a contiguous column range is a contiguous head range); embedding,
  norms and lm_head stay replicated — the residual stream ``x`` is
  replicated everywhere, which is what lets sampling run identically
  on every shard and the host read one logical token array.

Collective placement — ``ServingMesh.collective``:

- ``"psum"`` (default, bandwidth-optimal): o_proj/down_proj row-shard;
  each sub-block computes a partial product over its local heads /
  intermediate columns and ONE ``psum`` per sub-block (2 per layer)
  rebuilds the replicated residual. Greedy output is ROUNDOFF-parity
  vs the single-device engine: the all-reduce sums N partial matmul
  reductions in a different association order than the single fused
  reduction (the PR-6 mode=pallas precedent — documented, and the
  tests pin token-level agreement).
- ``"gather"`` (the documented bit-identical mode): o_proj/down_proj
  stay replicated; the per-shard attention heads / SwiGLU columns
  all-gather back to the full tensor FIRST, so every matmul sees
  exactly the single-device operands, shapes and reduction order.
  Greedy output is BIT-identical to the single-device engine (the
  tier-1 suite asserts it over a mixed-arrival stream).

Both placements run the one decode step (``generation._decode_step``)
per shard, and the kernel registry chooses its two launches from the
per-shard shapes: ``paged_attention_decode`` over the local heads, and
under "psum" ``decode_mlp_block`` over the local intermediate columns
(``residual=False`` returns the bare down-projection partial). The
"gather" placement runs the MLP composition: its bit-parity contract is
defined by the single-device op sequence.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.jax_compat import shard_map_norep

__all__ = ["ServingMesh", "tp_reject_reason", "normalize_mesh"]

_COLLECTIVES = ("psum", "gather")


def normalize_mesh(mesh) -> Optional["ServingMesh"]:
    """None | ServingMesh | 1-D jax Mesh | int tp -> ServingMesh|None —
    the one mesh-argument normalization serving.py and generation.py
    share."""
    if mesh is None:
        return None
    if isinstance(mesh, ServingMesh):
        return mesh
    if isinstance(mesh, int):
        return ServingMesh.make(tp=mesh)
    if isinstance(mesh, Mesh):
        axes = list(mesh.shape)
        if len(axes) != 1:
            raise ValueError(
                f"serving needs a 1-D mesh, got axes {dict(mesh.shape)}"
                " (wrap a ServingMesh to name the tp axis explicitly)")
        return ServingMesh(mesh, axis=axes[0])
    raise TypeError(f"mesh must be ServingMesh | jax Mesh | int | None,"
                    f" got {type(mesh).__name__}")


def tp_reject_reason(cfg, tp: int) -> Optional[str]:
    """Why ``cfg`` cannot shard over ``tp`` shards — None when it can.
    The clean fallback reason string: head-axis sharding needs every
    sharded dimension to divide evenly (a ragged shard would change
    shapes per device and break the single-program contract)."""
    if tp == 1:
        return None
    checks = (("num_key_value_heads", cfg.num_key_value_heads),
              ("num_attention_heads", cfg.num_attention_heads),
              ("intermediate_size", cfg.intermediate_size))
    for name, v in checks:
        if v % tp != 0:
            return (f"{name}={v} is not divisible by tp={tp}: head-axis "
                    f"sharding needs {name} % tp == 0 (use a divisor of "
                    f"{v}, or tp=1)")
    return None


@dataclasses.dataclass(frozen=True)
class ServingMesh:
    """The serving stack's tensor-parallel mesh: a 1-D device mesh, its
    axis name, and the collective placement. Holds the one definition
    of every NamedSharding the sharded programs use (pools, weights,
    replicated slot state), so serving.py / generation.py / the audit
    catalog cannot drift apart on layout.

    Build with :meth:`make` (first ``tp`` visible devices) or wrap an
    existing 1-D :class:`jax.sharding.Mesh`.
    """
    mesh: Mesh
    axis: str = "tp"
    collective: str = "psum"

    def __post_init__(self):
        if self.collective not in _COLLECTIVES:
            raise ValueError(f"collective must be one of {_COLLECTIVES},"
                             f" got {self.collective!r}")
        if len(self.mesh.shape) != 1 or self.axis not in self.mesh.shape:
            raise ValueError(
                f"ServingMesh needs a 1-D mesh over axis {self.axis!r}, "
                f"got mesh axes {dict(self.mesh.shape)}")

    @classmethod
    def make(cls, tp: Optional[int] = None, axis: str = "tp",
             collective: str = "psum", devices=None) -> "ServingMesh":
        devices = list(devices if devices is not None else jax.devices())
        tp = len(devices) if tp is None else int(tp)
        if tp < 1 or tp > len(devices):
            raise ValueError(f"tp={tp} but only {len(devices)} device(s)"
                             " visible")
        return cls(Mesh(np.array(devices[:tp]), (axis,)), axis=axis,
                   collective=collective)

    @property
    def tp(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def devices(self) -> list:
        return list(self.mesh.devices.flat)

    def split(self, first: int) -> Tuple["ServingMesh", "ServingMesh"]:
        """Split this mesh's device list into two disjoint ServingMesh
        groups: the first ``first`` devices and the remainder — the
        disaggregated engine's (prefill, decode) chip groups. Both keep
        this mesh's axis name and collective placement."""
        devs = self.devices
        if not 1 <= first < len(devs):
            raise ValueError(
                f"split(first={first}) needs 1 <= first < {len(devs)} "
                f"(the mesh has {len(devs)} device(s); both groups "
                "need at least one)")
        mk = lambda d: ServingMesh(                      # noqa: E731
            Mesh(np.array(d), (self.axis,)), axis=self.axis,
            collective=self.collective)
        return mk(devs[:first]), mk(devs[first:])

    def describe(self) -> Dict:
        return {"axis": self.axis, "tp": self.tp,
                "collective": self.collective}

    # -- shardings ----------------------------------------------------
    @property
    def pool_spec(self) -> P:
        """KV pools [L, N_pages, BS, KV, hd]: shard the KV-head axis."""
        return P(None, None, None, self.axis, None)

    @property
    def scale_spec(self) -> P:
        """int8 cache scales [L, KV]: shard with their pools."""
        return P(None, self.axis)

    @property
    def replicated(self) -> P:
        return P()

    def param_specs(self, cfg, params=None) -> Dict:
        """PartitionSpec tree for a llama param tree; pass ``params``
        when the tree may carry quantized weight leaves (the spec tree
        must mirror their dict structure)."""
        from ..models.llama import tp_param_specs
        return tp_param_specs(cfg, axis=self.axis,
                              collective=self.collective,
                              params=params)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def shard(self, tree, specs):
        """device_put a pytree onto the mesh under ``specs`` (a
        matching pytree of PartitionSpecs, or one spec for all)."""
        if isinstance(specs, P):
            sh = self.sharding(specs)
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sh), tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self.sharding(s)), tree,
            specs)

    def replicate(self, x):
        """Commit an array replicated onto the mesh (host-mirror
        re-uploads go through this so donation never needs a reshard)."""
        return jax.device_put(x, self.sharding(P()))

    # -- sharded program wiring ---------------------------------------
    def sharded_decode_fn(self, cfg, quant: bool, params=None):
        """The shard_map'd per-step decode forward: ``(params, tok,
        seq_lens, tables, k_pools, v_pools, *scales) -> (logits,
        k_pools, v_pools)`` — the ONE wiring of in/out specs around
        :func:`_tp_decode_step`, shared by ``ServingEngine``'s decode
        program and ``generate_paged``'s chunk runner so the two can
        never desync on layout or signature. ``params``: pass the real
        tree when it may carry quantized weight leaves (spec-structure
        mirroring)."""
        rep = self.replicated
        in_specs = (self.param_specs(cfg, params), rep, rep, rep,
                    self.pool_spec, self.pool_spec)
        if quant:
            in_specs += (self.scale_spec, self.scale_spec)

        def fwd(params, tok, seq_lens, tables, k_pools, v_pools, *sc):
            return _tp_decode_step(
                params, tok, cfg, k_pools, v_pools, tables, seq_lens,
                kv_scales=(tuple(sc) if sc else None), axis=self.axis,
                collective=self.collective)

        return shard_map_norep(fwd, self.mesh, in_specs,
                               (rep, self.pool_spec, self.pool_spec))

    # -- validation ---------------------------------------------------
    def reject_reason(self, cfg) -> Optional[str]:
        return tp_reject_reason(cfg, self.tp)

    def supports(self, cfg) -> Tuple[bool, str]:
        """(ok, reason) — the kernel-registry ``supports()`` idiom."""
        reason = self.reject_reason(cfg)
        if reason is not None:
            return False, reason
        return True, (f"tp={self.tp} over axis {self.axis!r} "
                      f"({self.collective} placement)")

    # -- flight-recorder inventory ------------------------------------
    def collective_inventory(self, cfg, B: int, chunk: int = 1) -> list:
        """The DECLARED per-step collectives of one sharded decode step
        (or one prefill chunk of ``chunk`` tokens): [(op, axis, shape,
        dtype)] with the per-step call count folded into the leading
        shape dim, so ``CommTask.nbytes`` counts the step's full
        logical payload. The serving engine replays this inventory
        through the bound flight recorder around each dispatched step —
        host-observed spans (the engine's sync-point philosophy), with
        the byte counters exact because the shapes are static."""
        L, D = cfg.num_hidden_layers, cfg.hidden_size
        dt = str(jnp.dtype(cfg.dtype))
        if self.collective == "psum":
            # one psum per sub-block: attn o-proj partial + MLP down
            # partial, each [B or B*chunk, D]
            return [("psum", self.axis, (2 * L, B * chunk, D), dt)]
        H, hd = cfg.num_attention_heads, cfg.head_dim
        F = cfg.intermediate_size
        return [
            ("all_gather", self.axis,
             (L, B * chunk, H // self.tp, hd), dt),
            ("all_gather", self.axis, (L, B * chunk, F // self.tp), dt),
        ]


# ---------------------------------------------------------------------------
# per-shard program bodies (run INSIDE shard_map: every array below is
# the LOCAL shard; tok/seq_lens/tables and the residual stream are
# replicated)
# ---------------------------------------------------------------------------
def _wshape(w):
    """Stored shape of a weight leaf (plain array or quantized dict —
    quantization/ptq.py format). Column counts are what the local-dim
    reads below need, and int4 packing never halves the output
    columns of q/k/v/gate/up."""
    if isinstance(w, dict):
        return (w["qw8"] if "qw8" in w else w["qw4"]).shape
    return w.shape


def _local_dims(params, cfg):
    """Local head/intermediate counts, read off the sharded arrays
    (shard_map hands the body local shapes, so the arrays themselves
    are the single source of truth for what this shard owns). The
    engine's fused ``qkv_proj`` leaf holds a shard's q, k and v heads
    side by side: ``cfg``'s ratio of them splits its width."""
    from ..ops.pallas.fused_decode_block import local_heads
    hd = cfg.head_dim
    layers = params["layers"]
    if "qkv_proj" in layers:
        H_loc, KV_loc = local_heads(
            layers["qkv_proj"].shape[2],
            (cfg.num_attention_heads, cfg.num_key_value_heads, hd))
    else:
        H_loc = _wshape(layers["q_proj"])[2] // hd
        KV_loc = _wshape(layers["k_proj"])[2] // hd
    F_loc = _wshape(layers["gate_proj"])[2]
    return H_loc, KV_loc, F_loc


def _lm_head(params):
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    return head


def _tp_decode_step(params, tok, cfg, k_pools, v_pools, block_tables,
                    seq_lens, kv_scales=None, axis="tp",
                    collective="psum"):
    """One tensor-parallel decode token per slot — the per-shard body
    of the engine's single jitted decode program: the ONE decode step
    (``generation._decode_step``, its layer loop included) over the
    local shards, with the collective placement documented in the
    module docstring.

    The "gather" placement's bit-parity contract IS the single-device op
    sequence, with the per-shard heads / SwiGLU columns all-gathered
    BEFORE o_proj / down_proj so those matmuls see exactly the
    single-device operands. No collective but the declared ones is
    emitted (the audited jaxpr carries exactly those).
    """
    from .generation import _decode_step

    return _decode_step(params, tok, cfg, k_pools, v_pools, block_tables,
                        seq_lens, kv_scales, axis=axis,
                        collective=collective)


def _tp_cached_layer(lp, x, sin, cos, cfg, kc, vc, pos, axis,
                     collective):
    """Tensor-parallel mirror of ``generation._cached_layer``: decoder
    block over S new tokens at absolute position ``pos``, reading and
    writing the LOCAL slice of the dense cache (kc/vc [B, T, KV_loc,
    hd]). Same op sequence per shard; the collective placement decides
    how the residual stream is rebuilt (module docstring)."""
    from ..inference.generation import _mm, _repeat_kv
    from ..ops import rms_norm as fused_rms_norm, swiglu as fused_swiglu
    from ..ops.pallas.fused_decode_block import qkv_project
    from ..ops.rope import apply_rope

    H, hd = cfg.num_attention_heads, cfg.head_dim
    b, s, _ = x.shape
    T = kc.shape[1]
    with jax.named_scope("layer/qkv"):
        h = fused_rms_norm(x, lp["input_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        q, k, v = qkv_project(h, lp, (H, cfg.num_key_value_heads, hd))
        H_loc, KV_loc = q.shape[2], k.shape[2]    # this shard's heads
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    with jax.named_scope("layer/kv_write"):
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, pos, 0, 0))
    with jax.named_scope("layer/attention"):
        rep = H_loc // KV_loc                 # groups survive sharding
        kk = _repeat_kv(kc, rep)              # [B, T, H_loc, hd]
        vv = _repeat_kv(vc, rep)
        scale = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * scale
        t_idx = jnp.arange(T)[None, None, None, :]
        q_idx = pos + jnp.arange(s)[None, None, :, None]
        scores = jnp.where(t_idx <= q_idx, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhst,bthd->bshd", probs,
                          vv.astype(jnp.float32))
    with jax.named_scope("layer/attn_out"):
        if collective == "gather":
            attn = jax.lax.all_gather(attn, axis, axis=2, tiled=True)
            attn = attn.astype(x.dtype).reshape(b, s, H * hd)
            x = x + _mm(attn, lp["o_proj"])
        else:
            attn = attn.astype(x.dtype).reshape(b, s, H_loc * hd)
            x = x + jax.lax.psum(_mm(attn, lp["o_proj"]), axis)
    with jax.named_scope("layer/mlp"):
        h = fused_rms_norm(x, lp["post_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        ff = fused_swiglu(_mm(h, lp["gate_proj"]), _mm(h, lp["up_proj"]))
        if collective == "gather":
            ff = jax.lax.all_gather(ff, axis, axis=2, tiled=True)
            x = x + _mm(ff, lp["down_proj"])
        else:
            x = x + jax.lax.psum(_mm(ff, lp["down_proj"]), axis)
    return x, kc, vc


def _tp_cached_forward(params, tokens, cfg, k_cache, v_cache, pos,
                       axis="tp", collective="psum"):
    """Tensor-parallel mirror of ``generation.cached_forward`` — the
    per-shard PREFILL body. ``k_cache``/``v_cache`` are the LOCAL dense
    views [L, B, T, KV_loc, hd]; tokens and the returned logits are
    replicated. Same program structure (one scan over layers), so
    bucketed chunked prefill keeps <=1 trace per bucket."""
    from ..ops import rms_norm as fused_rms_norm
    from ..ops.rope import build_rope_cache

    with jax.named_scope("embed"):
        x = jnp.take(params["embed_tokens"], tokens, axis=0)
    T = k_cache.shape[2]
    s = tokens.shape[1]
    with jax.named_scope("layer/qkv"):        # the rotary table
        sin_full, cos_full = build_rope_cache(T, cfg.head_dim,
                                              base=cfg.rope_theta)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, s, axis=0)
        cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, s, axis=0)

    def scan_fn(carry, xs):
        lp, kc, vc = xs
        x, kc, vc = _tp_cached_layer(lp, carry, sin, cos, cfg, kc, vc,
                                     pos, axis, collective)
        return x, (kc, vc)

    with jax.named_scope("layers"):
        x, (k_cache, v_cache) = jax.lax.scan(
            scan_fn, x, (params["layers"], k_cache, v_cache))
    with jax.named_scope("head"):
        x = fused_rms_norm(x, params["final_norm"].astype(x.dtype),
                           cfg.rms_norm_eps)
        return x @ _lm_head(params), k_cache, v_cache
