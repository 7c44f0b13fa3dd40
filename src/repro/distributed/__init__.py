from repro.distributed.sharding import (  # noqa: F401
    batch_axes, cache_pspec, constrain, current_mesh, make_sharding,
    paged_cache_pspec, param_pspec, pspec_tree, serving_mesh, use_mesh)
