"""Host time per round, in ms, in the program's `dispatch` span: rate
resolve, batch, and the jitted round's call up to its return
(`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.host_ms(ctx, "dispatch")
