"""Executables compiled or loaded from the persistent cache inside the
window (JAX's compile events), per scheduler tick, in the chat cell.
A steady loop that reuses its compiled programs reads 0."""

from chip.stats import compiles_per_tick


def read(rec):
    return compiles_per_tick(rec)
