"""Device time per round, in ms, of ops that no stage of the program's
round claims: what a scope lost in a refactor would add to.  With the
stage metrics it partitions the busy time of the round (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, None)
