"""Device time per round, in ms, of the round's `sbc.optimizer` scope:
the local optimizer's apply in every local step and the client's update
W_i' - W (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, "optimizer")
