"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Production target: TPU v5e pods — 16x16 = 256 chips per pod,
2 pods = 512 chips multi-pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    Since JAX 0.9 ``jax.make_mesh`` types axes ``Explicit`` by default,
    under which ``dynamic_slice`` of a stage-sharded pipeline output
    raises ``ShardingTypeError``.  Every mesh of this repo is built here,
    so the partitioner (not sharding-in-types) places the unsharded ops.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "tensor") if multi_pod else ("data", "tensor")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh with the same axis names (CPU tests/examples).

    Uses the canonical ``(data, tensor)`` names (core/parallel.py);
    sharding/specs.py accepts the historical "model" name as an alias.
    """
    return make_mesh((1, 1), ("data", "tensor"))


def make_data_mesh(dp: int, *, data_axis: str = "data"):
    """1D data-parallel mesh: ``dp`` replicas for the compressed gradient
    all-reduce (transport/collectives.py) around the SIMULATED boundary."""
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    if jax.device_count() < dp:
        raise RuntimeError(
            f"data-parallel mesh needs >= {dp} devices, have "
            f"{jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={dp} before jax init")
    return make_mesh((dp,), (data_axis,))


def make_dp_pipeline_mesh(dp: int, stages: int, *, data_axis: str = "data",
                          stage_axis: str = "stage"):
    """2D ``(data, stages)`` mesh: ``dp`` replicas each running a
    ``stages``-deep compressed pipeline.  Row r of the mesh is one replica;
    ``ppermute`` over ``stage_axis`` moves activations within a row, the
    DP gradient all-reduce rings over ``data_axis`` within a column.
    """
    if dp < 1 or stages < 1:
        raise ValueError(f"dp and stages must be >= 1, got ({dp}, {stages})")
    need = dp * stages
    if jax.device_count() < need:
        raise RuntimeError(
            f"2D DPxPP mesh needs >= {need} devices (dp={dp} x "
            f"stages={stages}), have {jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax init")
    return make_mesh((dp, stages), (data_axis, stage_axis))


def make_tensor_mesh(tp: int, *, tensor_axis: str = "tensor"):
    """1D tensor-parallel mesh: ``tp`` shards whose all-gather /
    reduce-scatter ring through the compressed wire
    (transport/tp_collectives.py)."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if jax.device_count() < tp:
        raise RuntimeError(
            f"tensor-parallel mesh needs >= {tp} devices, have "
            f"{jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp} before jax init")
    return make_mesh((tp,), (tensor_axis,))


def make_3d_mesh(dp: int, stages: int, tp: int, *, data_axis: str = "data",
                 stage_axis: str = "stage", tensor_axis: str = "tensor"):
    """3D ``(data, stage, tensor)`` mesh — all three of the paper's
    communication axes in one program.  Each (data, stage) cell holds a
    ``tp``-wide tensor-parallel group; ``ppermute`` over ``stage_axis``
    moves activations between stages within a (data, tensor) column, the
    TP all-gather/reduce-scatter rings over ``tensor_axis`` within a
    stage, and the DP gradient all-reduce rings over ``data_axis``.
    Axes of size 1 are kept (shard_map binds their names for free), so
    degenerate specs lower to the 2D/1D meshes' programs.
    """
    for k, v in (("dp", dp), ("stages", stages), ("tp", tp)):
        if v < 1:
            raise ValueError(f"{k} must be >= 1, got {v}")
    need = dp * stages * tp
    if jax.device_count() < need:
        raise RuntimeError(
            f"3D mesh needs >= {need} devices (dp={dp} x stages={stages} "
            f"x tp={tp}), have {jax.device_count()} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax init")
    return make_mesh((dp, stages, tp), (data_axis, stage_axis, tensor_axis))


_VOCAB_LEAVES = ("embed", "lm_head")


def train_state_shardings(tree, mesh, *, stage_axis: str = "stage",
                          tensor_axis: str = "tensor"):
    """NamedShardings for an LM's parameters, or for any tree that holds
    them under the same keys (the optimizer's moments), on the pipeline
    mesh of :func:`make_3d_mesh`.

    * a ``layers`` leaf ``(L, ...)`` splits its layer dim over
      ``stage_axis`` (stage s holds the contiguous block of layers that
      ``transformer.stack_layer_stages`` gives it) and its
      tensor-parallel dim over ``tensor_axis``
      (``transformer.tp_param_dims``: attention heads and the MLP width);
    * the vocabulary matrices (``embed``, and ``lm_head`` when untied)
      split their rows over both axes, so every chip holds
      ``1 / (stages * tp)`` of the vocabulary (replicated where the
      rows do not divide evenly);
    * everything else (norm scales, the step count) is replicated.

    The train step of ``make_lm_train_step(..., parallel=)`` returns its
    state in the same layout, so donated state stays in place."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.transformer import tp_param_dims

    def spec(path, leaf, tp_dim):
        keys = [str(getattr(k, "key", k)) for k in path]
        entries = [None] * leaf.ndim
        if "layers" in keys:
            entries[0] = stage_axis
            if tp_dim >= 0:
                entries[tp_dim] = tensor_axis
        elif (keys and keys[-1] in _VOCAB_LEAVES
              and leaf.shape[0] % (mesh.shape[stage_axis]
                                   * mesh.shape[tensor_axis]) == 0):
            entries[0] = (stage_axis, tensor_axis)
        while entries and entries[-1] is None:   # the step's own spelling
            entries.pop()
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(spec, tree, tp_param_dims(tree))
