"""TPU v5e hardware constants (per chip) — roofline targets."""

PEAK_FLOPS_BF16 = 197e12  # FLOP/s
PEAK_OPS_INT8 = 393e12  # OP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link (single-direction, conservative 1-link model)
HBM_BYTES = 16 * 2**30  # capacity per chip
VMEM_BYTES = 16 * 2**20
