"""A metric added by a file alone: output tokens per scheduler tick."""


def read(rec):
    return rec["out_tokens"] / len(rec["steps"]) if rec["steps"] else None
