"""Host time per round, in ms, in the program's `encode` span less any
`fetch` inside it: the host Golomb pack of client 0's upload.  Nothing
to read where the program meters no upload (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.host_ms(ctx, "encode")
