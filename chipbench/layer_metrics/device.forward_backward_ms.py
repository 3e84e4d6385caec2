"""Device time per round, in ms, of the round's `sbc.forward_backward`
scope: the local steps' loss and gradient with their scan loop, each op's
own time joined to the scope through the compiled HLO (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, "forward_backward")
