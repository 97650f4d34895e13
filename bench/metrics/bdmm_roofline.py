"""Kernels (kernels/bdmm.py): roofline share of the block-diagonal matmul
calls in the decode and chunk programs of the traced slice, in %."""

import readers

OPS = r"^bdmm\."


def read(ctx):
    return readers.roofline(ctx, "bdmm", OPS)
