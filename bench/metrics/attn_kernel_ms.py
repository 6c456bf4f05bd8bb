"""attn_kernel_ms: device milliseconds per step in the Pallas attention
kernels (fwd, dq, dkv; names in bench/kernels/doc_attention.json), summed
over devices and divided by chips.  Layer: attention kernels.  Device
trace."""

from harness.trace import kernel_names


def read(rec):
    if rec.trace is None or not rec.traced:
        return None
    s = rec.trace.op_seconds(kernel_names(rec.bench_dir, "doc_attention"))
    if s <= 0:
        return None
    return 1e3 * s / rec.chips / len(rec.traced)
