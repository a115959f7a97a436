"""The weights of ``dense_decoder_stacked``, made sharded in place over
the first ``TP`` devices: a model that does not fit one chip is never
whole on one. Same seed, same values: ``out_shardings`` changes where
the one jitted call leaves its result, not what it draws.

The layout is the program's own (``ServingMesh.param_specs``: the
engine would lay a whole tree out the same way, from one device), so
the engine's own placement of the tree is then a no-op; the plain
reference reads the same sharded arrays."""
import types

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from benchmarks.weights import dense_decoder_stacked as base

TP = 4
count, shapes = base.count, base.shapes


def shardings(model):
    from paddle_tpu.inference.tp import ServingMesh
    mesh = ServingMesh.make(tp=TP)
    specs = mesh.param_specs(types.SimpleNamespace(
        tie_word_embeddings=bool(model.get("tie_word_embeddings", False))))
    return jax.tree_util.tree_map(
        mesh.sharding, specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec))


def make(model, seed, dtype=jnp.bfloat16):
    return base.make(model, seed, dtype, out_shardings=shardings(model))
