"""All output tokens emitted in the window over the window's length."""


def read(rec):
    return rec["out_tokens"] / rec["window_s"]
