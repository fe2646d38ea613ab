"""Operations the served requests need (one history encode plus one tower
row per candidate, counted from the configuration's shapes), over device
busy time times the configuration's peak, in %. It counts what the
requests need, not what the program computes: a step that encodes a
history once per candidate reads low."""


def read(r):
    return 100.0 * r.flops / (r.busy_s * r.peak * r.chips) if r.flops and r.busy_s > 0 else None
