"""Forward and backward FLOPs per token from the shapes (recomputation
not counted) times the traced run's tokens per second, over the peak of
all the chips the step runs on."""

from chip import flops


def read(rec):
    if not rec.get("trace") or not rec["train_tokens"]:
        return None
    rate = rec["train_tokens"] / rec["window_s"]
    per_token = flops.train_flops_per_token(rec["dims"], rec["seq_len"])
    peak = rec["chips"] * rec["peaks"].flops_bf16
    return 100.0 * per_token * rate / peak
