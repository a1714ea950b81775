"""Device time under the named scope `attention` (the decode attention
over the K/V cache and its write) per traced pure-decode tick."""

from chip.stats import decode_scope_ms


def read(rec):
    return decode_scope_ms(rec, "attention")
