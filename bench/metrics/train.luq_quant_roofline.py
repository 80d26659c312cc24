"""Roofline share of the ``luq_quant`` Pallas kernel: the least time its
calls in the traced stretch could take, from the bytes each call moves
(the operand and result shapes of its instruction in the compiled program,
as the trace names the op) at the chip's HBM bandwidth, over the kernel's
summed device time.  The kernel is elementwise, so bandwidth bounds it."""
from bench import counts, kernels


def read(record):
    if record.get("train") is None or record.get("trace") is None \
            or record.get("peaks") is None:
        return None
    return kernels.roofline_share(record["trace"], "luq",
                                  record["peaks"]["hbm_bytes_per_s"],
                                  counts.instruction_bytes)
