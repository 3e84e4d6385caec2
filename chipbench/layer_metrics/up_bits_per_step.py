"""Upstream bits per client per local step: the program's own count of
client 0's packed upload in round 0 (``measured_bits_per_client``, the
real bytes its wire encoder wrote), over the round's local steps.
Nothing to read where the program meters no upload."""


def read(ctx):
    bits = ctx.counters.get("measured_bits_per_client")
    if bits is None:
        return None
    return bits / ctx.traffic["delay"]
