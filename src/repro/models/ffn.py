"""Feed-forward blocks (dense MLPs) — the paper's primary compression target.

Supports gated (SwiGLU/GeGLU) and plain (GELU/ReLU) MLPs; every projection is
an MPD-compressible :class:`Linear`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.policy import CompressionPolicy
from .linear import Linear


@dataclasses.dataclass(frozen=True)
class FFNSpec:
    d_model: int
    d_ff: int
    kind: str = "swiglu"  # swiglu | gelu | relu
    use_bias: bool = False
    w_up: Linear = None
    w_gate: Linear = None  # None for non-gated kinds
    w_down: Linear = None

    @staticmethod
    def make(policy: CompressionPolicy, d_model, d_ff, kind="swiglu",
             use_bias=False, seed_salt=0, fuse_perms=False) -> "FFNSpec":
        """``fuse_perms`` (beyond-paper §Perf; mechanism from paper Fig 3):
        up/gate share one mask (one input gather, outputs stay in packed
        order — valid because the elementwise gate commutes with any fixed
        permutation) and down's input permutation is chosen as the inverse
        of up's output permutation, so the d_ff-sized inner gathers vanish
        and the hidden activation never leaves block order (no reshard)."""
        gated = kind == "swiglu"
        if not fuse_perms:
            return FFNSpec(
                d_model, d_ff, kind, use_bias,
                w_up=Linear.make(policy, d_model, d_ff, "mlp", use_bias=use_bias,
                                 seed_salt=seed_salt * 3 + 0, axes=("embed", "ffn")),
                w_gate=(Linear.make(policy, d_model, d_ff, "mlp", use_bias=use_bias,
                                    seed_salt=seed_salt * 3 + 1, axes=("embed", "ffn"))
                        if gated else None),
                w_down=Linear.make(policy, d_ff, d_model, "mlp", use_bias=use_bias,
                                   seed_salt=seed_salt * 3 + 2, axes=("ffn", "embed")),
            )
        import numpy as _np
        from repro.core.mask import make_mask_spec
        from repro.core import permute as _perm
        m_up = policy.plan(d_model, d_ff, "mlp", seed_salt=seed_salt * 3 + 0)
        m_down = policy.plan(d_ff, d_model, "mlp", seed_salt=seed_salt * 3 + 2)
        if m_up is not None and m_down is not None and m_up.nb == m_down.nb:
            m_down = make_mask_spec(d_ff, d_model, m_down.nb,
                                    seed=m_down.seed,
                                    in_perm=m_up.out_perm,   # cancels
                                    out_perm=m_down.out_perm)
            up = Linear.make(policy, d_model, d_ff, "mlp", use_bias=use_bias,
                             axes=("embed", "ffn"), mask_override=m_up,
                             skip_out_perm=True)
            gate = (Linear.make(policy, d_model, d_ff, "mlp", use_bias=use_bias,
                                axes=("embed", "ffn"), mask_override=m_up,
                                skip_out_perm=True) if gated else None)
            down = Linear.make(policy, d_ff, d_model, "mlp", use_bias=use_bias,
                               axes=("ffn", "embed"), mask_override=m_down,
                               skip_in_perm=True)
            return FFNSpec(d_model, d_ff, kind, use_bias, up, gate, down)
        return FFNSpec.make(policy, d_model, d_ff, kind, use_bias, seed_salt,
                            fuse_perms=False)

    def init(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 3)
        p = {"w_up": self.w_up.init(ks[0], dtype),
             "w_down": self.w_down.init(ks[2], dtype)}
        if self.w_gate is not None:
            p["w_gate"] = self.w_gate.init(ks[1], dtype)
        return p

    def axes(self):
        a = {"w_up": self.w_up.axes(), "w_down": self.w_down.axes()}
        if self.w_gate is not None:
            a["w_gate"] = self.w_gate.axes()
        return a

    # ----------------------------------------------------------- fused route
    def fused_packed(self) -> bool:
        """True when the whole MLP can execute as ONE block-diagonal fused
        kernel (:func:`repro.kernels.ops.fused_ffn`): all projections packed
        with the inner permutations cancelled at build/export time, so the
        hidden stays in block order and blocks are fully independent."""
        up, gate, down = self.w_up, self.w_gate, self.w_down
        if up is None or down is None:
            return False
        su, sd = up.spec, down.spec
        if not (su.mode == "packed" and sd.mode == "packed"
                and su.mask is not None and sd.mask is not None):
            return False
        if not (su.skip_out_perm and sd.skip_in_perm):
            return False
        if su.mask.nb != sd.mask.nb:
            return False
        if gate is not None:
            import numpy as np
            sg = gate.spec
            if not (sg.mode == "packed" and sg.mask is not None
                    and sg.skip_out_perm and sg.mask.nb == su.mask.nb
                    and np.array_equal(sg.mask.in_perm, su.mask.in_perm)):
                return False
        return True

    def _packed_bias(self, lin, p):
        """Layer bias in the kernel's packed output order: the ``bp`` leaf
        the serving engine makes when it loads its weights, or ``b``
        gathered on this call (:func:`repro.core.mpd.packed_bias`)."""
        if not lin.spec.use_bias:
            return None
        from repro.core.mpd import packed_bias
        return packed_bias(lin.spec, p, p["b"])

    def _apply_fused(self, params, x):
        from repro.core import fold as fold_lib
        from repro.kernels import ops
        up, gate, down = self.w_up, self.w_gate, self.w_down
        xp = fold_lib.pack_inputs(up.spec.mask, x, skip=up.spec.skip_in_perm)
        act = {"swiglu": "silu", "gelu": "gelu", "relu": "relu"}[self.kind]
        biases = dict(
            b_up=self._packed_bias(up, params["w_up"]),
            b_gate=(self._packed_bias(gate, params["w_gate"])
                    if gate is not None else None),
            b_down=self._packed_bias(down, params["w_down"]))
        from repro.kernels.quant import is_quantized
        if is_quantized(params["w_up"]):
            # quantized deployment artifact: all three projections carry
            # int8 blocks + scales (the quantize pass converts them
            # together), routed to the int8 fused kernel
            y = ops.fused_ffn_quant(
                xp, params["w_up"]["w_q"], params["w_down"]["w_q"],
                s_up=params["w_up"]["w_scale"],
                s_down=params["w_down"]["w_scale"],
                w_gate=params["w_gate"]["w_q"] if gate is not None else None,
                s_gate=(params["w_gate"]["w_scale"]
                        if gate is not None else None),
                activation=act, **biases)
        else:
            y = ops.fused_ffn(
                xp, params["w_up"]["w"], params["w_down"]["w"],
                w_gate=params["w_gate"]["w"] if gate is not None else None,
                activation=act, **biases)
        y = fold_lib.unpack_outputs(down.spec.mask, y,
                                    skip=down.spec.skip_out_perm)
        if down.out_axis is not None and y.ndim >= 2:
            from repro.dist.sharding import shard
            y = shard(y, "batch", *([None] * (y.ndim - 2) + [down.out_axis]))
        return y

    def apply(self, params, x):
        if self.fused_packed():
            return self._apply_fused(params, x)
        # epilogues ride the projection dispatch (fused into the kernels on
        # the compressed modes) instead of composing as separate XLA ops
        if self.kind == "swiglu":
            h = self.w_up.apply(params["w_up"], x)
            g = self.w_gate.apply(params["w_gate"], x, activation="silu")
            h = g * h
        elif self.kind in ("gelu", "relu"):
            h = self.w_up.apply(params["w_up"], x, activation=self.kind)
        else:
            raise ValueError(self.kind)
        return self.w_down.apply(params["w_down"], h)
