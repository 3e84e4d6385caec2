"""Device time per round, in ms, of the round's `sbc.quantize` scope:
error feedback and the quantizer (residual add, binarisation mean, the
scatter of the update's survivors, residual update, and the layout copies
of the residual the compiler adds) (`stage_split`)."""
from chipbench import stage_split


def read(ctx):
    return stage_split.device_ms(ctx, "quantize")
