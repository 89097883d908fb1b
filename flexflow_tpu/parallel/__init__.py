from flexflow_tpu.parallel.pconfig import ParallelConfig  # noqa: F401
from flexflow_tpu.parallel.mesh import make_mesh, default_mesh  # noqa: F401


def shard_entries(mesh, axis_map, shape, dims):
    """For each tensor dim in `dims`: the PartitionSpec entry (axis name,
    tuple of names, or None) the strategy shards it over — None when the
    dim is unsharded OR its size is not divisible by the mapped mesh degree
    (that group alone degrades to GSPMD padding while the rest keeps its
    parallelism). Shared by every per-shard Pallas lowering
    (ops/attention._flash_dense, ops/norm.AddLayerNorm)."""
    out = {}
    for d in dims:
        axes = [ax for ax, dd in (axis_map or {}).items()
                if dd == d and mesh.shape[ax] > 1]
        deg = 1
        for ax in axes:
            deg *= mesh.shape[ax]
        if shape[d] % deg != 0:
            axes = []
        if not axes:
            out[d] = None
        else:
            out[d] = axes[0] if len(axes) == 1 else tuple(axes)
    return out

