"""The whole step's share of the chips' peak: samples per second over the
traced window times the operations one sample's forward and backward
passes require (the configuration's ``flops_per_sample``), over chips
times the peak bf16 rate.  It bounds any kernel's gain once that kernel
leaves the path."""


def read(ctx):
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * ctx.samples_per_s * ctx.flops_per_sample / peak
