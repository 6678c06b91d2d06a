from .sharding import (  # noqa: F401
    collective_ops,
    collective_profile,
    make_mesh,
    make_mesh_2d,
    make_multihost_mesh,
    peer_spec,
    shard_state,
    state_shardings,
)
