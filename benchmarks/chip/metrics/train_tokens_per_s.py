"""Tokens of all training steps completed in the window over its length."""


def read(rec):
    return rec["train_tokens"] / rec["window_s"]
