"""Device time under the named scopes `mm.*` (the layers' planned
matmuls; the LM head's sits under `lm_head`) per traced pure-decode
tick."""

from chip.stats import decode_scope_ms


def read(rec):
    return decode_scope_ms(rec, "mm")
