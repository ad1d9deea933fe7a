"""Kernels: roofline share of the field-matmul kernels (plain and fused),
from the device trace and the operations' shapes."""
import readers


def read(run):
    return readers.field_matmul_roofline(run)
