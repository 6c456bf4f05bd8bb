"""attn_roofline: least time over measured time of the Pallas attention
kernels in the traced steps, in percent.  The least time is the larger of
the required attention FLOPs (12 x heads x head_dim x causal pairs per
layer, no recomputation) over the bf16 peak and the bytes that must move
(Q, K, V, O, dO, dQ, dK, dV and the row statistics) over the HBM peak,
both counted by bench/flops/; the measured time is the kernels' device
time summed over all devices.  Which bound applies is in bound_of().
Layer: attention kernels.  Device trace."""

from harness.trace import kernel_names


def bound_of(rec):
    """(least seconds, "flops" or "bytes") of the traced steps."""
    fl = sum(rec.flops.attention_flops(rec.config, r["pairs"])
             for r in rec.traced)
    by = sum(rec.flops.attention_bytes(rec.config, r["tokens"])
             for r in rec.traced)
    t_f = fl / rec.peaks["bf16_flops"]
    t_b = by / rec.peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def read(rec):
    if rec.trace is None or not rec.traced:
        return None
    measured = rec.trace.op_seconds(kernel_names(rec.bench_dir,
                                                   "doc_attention"))
    if measured <= 0:
        return None
    return 100.0 * bound_of(rec)[0] / measured
