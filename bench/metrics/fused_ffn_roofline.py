"""Kernels (kernels/fused_ffn.py): roofline share of the fused packed MLP
calls in the decode and chunk programs of the traced slice, in %."""

import readers

OPS = r"^fused_ffn"


def read(ctx):
    return readers.roofline(ctx, "fused_ffn", OPS)
