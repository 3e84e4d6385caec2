"""Host time per round, in ms, in the program's `fetch` span: the
blocking copies of client 0's compressed upload to the host.  Nothing to
read where the program meters no upload (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.host_ms(ctx, "fetch")
