"""Device time per round, in ms, of the round's `sbc.apply` scope: the
mean over clients, the weight update, the momentum mask and the round's
metrics (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, "apply")
