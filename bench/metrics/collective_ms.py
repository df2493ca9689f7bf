"""Device time per epoch of the collectives of the sharded epoch
(core/sharded.py: psum over data, all_to_all and all_gather over model)."""
from per_layer import per_unit_ms


def read(ctx):
    return per_unit_ms(ctx, "collective")
