"""train_tokens_per_s: tokens of all steps completed in the window (global
batch x sequence length per step) over the time from the window's start to
the end of its last step.  Host clock."""


def read(rec):
    if not rec.window:
        return None
    return sum(r["tokens"] for r in rec.window) / rec.window_s
