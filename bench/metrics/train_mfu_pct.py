"""train_mfu_pct: model FLOPs of the window's steps over window time x
chips x the chip's bf16 peak, in percent.  The FLOPs are counted by the
model family's file under bench/flops/ (no recomputation).  Layer: train
step.  Host clock."""


def read(rec):
    if not rec.window:
        return None
    fl = sum(rec.flops.model_flops(rec.config, r["tokens"], r["pairs"])
             for r in rec.window)
    return 100.0 * fl / (rec.window_s * rec.chips * rec.peaks["bf16_flops"])
