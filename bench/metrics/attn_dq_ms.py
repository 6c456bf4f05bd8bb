"""attn_dq_ms: device milliseconds per traced step in the Pallas kernel
``doc_attn_dq`` (kernels/doc_attention.py), matched by its operation's
short name without the ``.N`` suffix, summed over devices and divided by
chips.  Layer: attention kernels.  Device trace."""

from harness.named import kernel_ms


def read(rec):
    return kernel_ms(rec, "doc_attn_dq")
