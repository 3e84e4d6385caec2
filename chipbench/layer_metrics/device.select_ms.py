"""Device time per round, in ms, of the round's `sbc.select` scope: the
selector the policy resolves (SBC: the two-sided top-k per tensor).
Nothing to read where the round selects nothing (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, "select")
