"""Pallas TPU kernels for MPDCompress hot spots.

- ``bdmm``          : block-diagonal matmul (packed inference/training form;
                      int8-weight + decode-shaped small-m variants inside)
- ``masked_matmul`` : fused mask∘W matmul (paper-faithful training, Fig 2)
- ``fused_ffn``     : block-diagonal fused MLP (perm-fused packed FFN path;
                      int8-weight variant inside)
- ``paged_attention``: decode-step attention over the paged KV pool
                      (scalar-prefetched block tables, online softmax)
- ``paged_prefill`` : flash-style chunked-prefill attention over the same
                      pool (per-tile causal page skip — KV read ∝ depth)
- ``quant``         : symmetric per-output-channel int8/int4 block
                      quantization (scales, nibble packing, error stats)
- ``tiling``        : shared grid-tiling policy (pad, don't degrade)
- ``ops``           : jit'd differentiable wrappers + backend routing
- ``ref``           : pure-jnp oracles

Bias/activation epilogues execute inside every kernel; ``ops`` carries the
custom VJPs over the fused forms.
"""

from . import ops, ref  # noqa: F401
