"""Top-level model zoo assembly.

One :class:`ModelConfig` describes every assigned architecture via a
repeating layer ``pattern`` (e.g. ``("attn",)`` for dense transformers,
``("rwkv",)`` for RWKV-6, Jamba's 8-layer hybrid period). Layers are stacked
with ``lax.scan`` over *periods* (params stacked on a leading axis) so the
HLO stays one-period-sized regardless of depth — required to compile 64-80
layer configs on this container, and the production-standard layout anyway.

Entry points:
  * ``init(key)``                         -> params
  * ``train_loss(params, batch)``         -> scalar loss (+aux)
  * ``forward(params, inputs)``           -> hidden states (no head)
  * ``logits(params, inputs)``            -> LM head outputs
  * ``prefill(params, inputs, caches)``   -> (logits_last, caches)
  * ``decode_step(params, token, caches)``-> (logits, caches)

Losses use a *chunked* vocab-parallel cross-entropy (lse/labels gathered per
sequence chunk with a rematerialized body) so the (B,T,V) logits tensor is
never alive at once — V can be 256k on the assigned archs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import CompressionPolicy
from repro.dist.sharding import shard
from . import attention as attn_lib
from . import layers
from .ffn import FFNSpec
from .linear import Linear
from .mamba import MambaSpec
from .moe import MoESpec
from .rwkv import RWKVSpec

BLOCK_KINDS = ("attn", "attn_moe", "mamba", "mamba_moe", "rwkv")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 2
    n_kv_heads: int = 2
    d_ff: int = 256
    vocab: int = 256
    head_dim: int = 0               # 0 -> d_model // n_heads
    norm: str = "rms"               # rms | ln | none (olmo)
    ffn_kind: str = "swiglu"        # swiglu | gelu | relu
    use_bias: bool = False
    causal: bool = True             # False -> encoder (hubert)
    rope: str = "rope"              # rope | mrope | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    pattern: Tuple[str, ...] = ("attn",)
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_shared_gated: bool = False
    moe_capacity: float = 1.25
    moe_experts_pad: int = 0    # physical expert padding for EP divisibility
    # SSM families
    rwkv_head_dim: int = 64
    mamba_expand: int = 2
    # IO
    frontend: str = "token"         # token | embed (audio/vlm stubs feed (B,T,D))
    q_chunk: int = 128
    loss_chunk: int = 512           # CE sequence chunk
    dtype: str = "float32"
    aux_loss_weight: float = 0.01
    remat: str = "block"            # block | none
    # MPDCompress policy
    mpd_c: int = 1
    mpd_mode: str = "packed"        # packed | masked_dense
    mpd_min_block: int = 8
    mpd_permuted: bool = True
    mpd_seed: int = 0
    mpd_per_kind: Tuple[Tuple[str, int], ...] = ()
    mpd_fuse: bool = False          # beyond-paper: Fig 3 permutation fusion

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def policy(self) -> CompressionPolicy:
        return CompressionPolicy(
            c=self.mpd_c, per_kind=dict(self.mpd_per_kind) or None,
            min_block=self.mpd_min_block, permuted=self.mpd_permuted,
            seed=self.mpd_seed, mode=self.mpd_mode,
        )

    @property
    def jdtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[self.dtype]


# the leaves of a paged attention cache that hold its page pool
POOL_LEAVES = ("kp", "vp")


def scan_layers(body, x, params, cache):
    """``lax.scan`` of ``body(x, p, c, layer)`` over stacked layers, under
    the named scopes a device trace reads: every op carries ``layer_scan``,
    and the body's own ops ``layer`` inside it, so the ops the scan adds to
    move each layer's parameters and cache rows in and out are those
    without ``layer``.

    ``p`` is the layer's slice of ``params``, ``layer`` its index. The
    cache's page pools (``POOL_LEAVES``) ride the scan's carry whole: ``c``
    holds them with their layer axis, and the body writes and reads its own
    layer's pages in place, so no step copies a layer's pool out or back.
    Every other cache leaf (``pos``, contiguous K/V, recurrent state) is
    sliced per layer and stacked back."""
    pools = {k: v for k, v in cache.items() if k in POOL_LEAVES}
    rows = {k: v for k, v in cache.items() if k not in POOL_LEAVES}

    def scoped(carry, per_layer):
        x, pools = carry
        p, c, layer = per_layer
        with jax.named_scope("layer"):
            x, c2 = body(x, p, {**c, **pools}, layer)
        return ((x, {k: c2[k] for k in pools}),
                {k: v for k, v in c2.items() if k not in pools})

    n = jax.tree.leaves(params)[0].shape[0]
    with jax.named_scope("layer_scan"):
        (x, pools), rows = jax.lax.scan(
            scoped, (x, pools), (params, rows, jnp.arange(n, dtype=jnp.int32)))
    return x, {**rows, **pools}


class Model:
    """Functional model: static specs here, params as plain pytrees."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.n_layers % len(cfg.pattern) == 0, (cfg.n_layers, cfg.pattern)
        self.cfg = cfg
        self.n_periods = cfg.n_layers // len(cfg.pattern)
        pol = cfg.policy
        self.block_specs = [
            self._make_block(pol, kind, i) for i, kind in enumerate(cfg.pattern)
        ]
        self.unembed = Linear.make(pol, cfg.d_model, cfg.vocab, "unembed",
                                   axes=("embed", "vocab"))

    # ------------------------------------------------------------------ specs
    def _make_block(self, pol: CompressionPolicy, kind: str, idx: int):
        cfg = self.cfg
        assert kind in BLOCK_KINDS, kind
        spec: Dict[str, Any] = {"kind": kind}
        if kind in ("attn", "attn_moe"):
            spec["mixer"] = attn_lib.AttentionSpec.make(
                pol, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                causal=cfg.causal, rope=cfg.rope, rope_theta=cfg.rope_theta,
                mrope_sections=cfg.mrope_sections, q_chunk=cfg.q_chunk,
                use_bias=cfg.use_bias, seed_salt=idx + 1,
                fuse_perms=cfg.mpd_fuse,
            )
        elif kind in ("mamba", "mamba_moe"):
            spec["mixer"] = MambaSpec.make(pol, cfg.d_model, cfg.mamba_expand,
                                           seed_salt=idx + 1)
        elif kind == "rwkv":
            spec["mixer"] = RWKVSpec.make(pol, cfg.d_model, cfg.d_ff,
                                          cfg.rwkv_head_dim, seed_salt=idx + 1)
        if kind.endswith("_moe"):
            spec["ffn"] = MoESpec.make(
                pol, cfg.d_model, cfg.moe_d_ff, cfg.moe_experts, cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity,
                d_ff_shared=cfg.moe_shared_d_ff, shared_gated=cfg.moe_shared_gated,
                mode=cfg.mpd_mode if cfg.mpd_c > 1 else "dense",
                seed_salt=idx + 100, n_experts_padded=cfg.moe_experts_pad,
            )
        elif kind in ("attn", "mamba"):
            spec["ffn"] = FFNSpec.make(pol, cfg.d_model, cfg.d_ff, cfg.ffn_kind,
                                       cfg.use_bias, seed_salt=idx + 100,
                                       fuse_perms=cfg.mpd_fuse)
        else:
            spec["ffn"] = None  # rwkv: channel-mix lives inside the mixer spec
        return spec

    # ----------------------------------------------------------------- params
    def _init_block(self, spec, key, dtype):
        ks = jax.random.split(key, 4)
        p = {
            "norm1": layers.init_norm(self.cfg.norm, self.cfg.d_model, jnp.float32),
            "mixer": spec["mixer"].init(ks[0], dtype),
            "norm2": layers.init_norm(self.cfg.norm, self.cfg.d_model, jnp.float32),
        }
        if spec["ffn"] is not None:
            p["ffn"] = spec["ffn"].init(ks[1], dtype)
        return p

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = cfg.jdtype
        keys = jax.random.split(key, len(self.block_specs) + 3)
        params: Dict[str, Any] = {}
        if cfg.frontend == "token":
            params["embed"] = layers.init_embedding(keys[0], cfg.vocab,
                                                    cfg.d_model, dtype)
        params["blocks"] = []
        for i, spec in enumerate(self.block_specs):
            pk = jax.random.split(keys[i + 1], self.n_periods)
            params["blocks"].append(
                jax.vmap(lambda k: self._init_block(spec, k, dtype))(pk)
            )
        params["final_norm"] = layers.init_norm(cfg.norm, cfg.d_model, jnp.float32)
        params["unembed"] = self.unembed.init(keys[-1], dtype)
        return params

    def _block_axes(self, spec):
        a = {
            "norm1": {k: (None,) for k in
                      layers.init_norm(self.cfg.norm, 1)},
            "mixer": spec["mixer"].axes(),
            "norm2": {k: (None,) for k in layers.init_norm(self.cfg.norm, 1)},
        }
        if spec["ffn"] is not None:
            a["ffn"] = spec["ffn"].axes()
        return a

    def axes(self) -> Dict[str, Any]:
        """Logical-axis tree matching :meth:`init` (leading 'layers' axis on
        stacked block params)."""
        cfg = self.cfg
        add_layer = lambda t: jax.tree.map(
            lambda names: ("layers",) + tuple(names), t,
            is_leaf=lambda x: isinstance(x, tuple))
        a: Dict[str, Any] = {}
        if cfg.frontend == "token":
            a["embed"] = {"table": ("vocab", None)}
        a["blocks"] = [add_layer(self._block_axes(s)) for s in self.block_specs]
        a["final_norm"] = {k: (None,) for k in layers.init_norm(cfg.norm, 1)}
        a["unembed"] = self.unembed.axes()
        return a

    # ---------------------------------------------------------------- forward
    def _apply_block(self, spec, p, x, state=None):
        """One block, full-sequence mode. Returns (x, aux, new_state)."""
        cfg = self.cfg
        kind = spec["kind"]
        aux = jnp.zeros((), jnp.float32)
        h = layers.apply_norm(cfg.norm, p["norm1"], x)
        if kind in ("attn", "attn_moe"):
            x = x + attn_lib.apply_train(spec["mixer"], p["mixer"], h)
            new_state = None
        elif kind in ("mamba", "mamba_moe"):
            y, new_state = spec["mixer"].apply(p["mixer"], h, state)
            x = x + y
        else:  # rwkv
            mix = spec["mixer"]
            st = state if state is not None else mix.init_state(x.shape[0], x.dtype)
            y, s_new, x_tm = mix.time_mix(p["mixer"], h, st["S"], st["x_tm"])
            x = x + y
            h2 = layers.apply_norm(cfg.norm, p["norm2"], x)
            y2, x_cm = mix.channel_mix(p["mixer"], h2, st["x_cm"])
            x = x + y2
            return shard(x, "batch", None, None), aux, {
                "S": s_new, "x_tm": x_tm, "x_cm": x_cm}
        h2 = layers.apply_norm(cfg.norm, p["norm2"], x)
        if kind.endswith("_moe"):
            y2, aux = spec["ffn"].apply(p["ffn"], h2)
        else:
            y2 = spec["ffn"].apply(p["ffn"], h2)
        x = x + y2
        return shard(x, "batch", None, None), aux, new_state

    def forward(self, params, inputs):
        """Full-sequence trunk. inputs: (B,T) int tokens or (B,T,D) embeds."""
        cfg = self.cfg
        x = self._embed_inputs(params, inputs)
        aux_total = jnp.zeros((), jnp.float32)

        def period_body(carry, per_period):
            x, aux = carry
            for spec, p in zip(self.block_specs, per_period):
                x, a, _ = self._apply_block(spec, p, x)
                aux = aux + a
            return (x, aux), None

        body = period_body
        if cfg.remat == "block":
            body = jax.checkpoint(period_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         tuple(params["blocks"]))
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        return x, aux_total

    def _embed_inputs(self, params, inputs):
        cfg = self.cfg
        if cfg.frontend == "token":
            x = layers.embed(params["embed"], inputs) * float(np.sqrt(cfg.d_model))
        else:
            x = inputs.astype(cfg.jdtype)
        return shard(x, "batch", None, None)

    def _stream(self, x):
        """The residual stream as the serving programs carry it: float32
        whatever the compute dtype. Rounded to bfloat16 at each of its
        2 x n_layers adds, it moves the logits of a 36-layer model
        (Granite-8B-Code) about half as far as fp8 weights do; the
        projections still take their inputs in the compute dtype
        (:meth:`_norm`)."""
        return x.astype(jnp.float32)

    def _norm(self, p, x):
        """The configured norm of the residual stream ``x``, in the compute
        dtype."""
        return layers.apply_norm(self.cfg.norm, p, x).astype(self.cfg.jdtype)

    def logits(self, params, inputs):
        x, _ = self.forward(params, inputs)
        return self.unembed.apply(params["unembed"], x)

    # ------------------------------------------------------------------- loss
    def _ce_chunk(self, params, x_chunk, labels_chunk):
        lg = self.unembed.apply(params["unembed"], x_chunk).astype(jnp.float32)
        lg = shard(lg, "batch", None, "vocab")
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels_chunk[..., None], axis=-1)[..., 0]
        return lse - ll  # (B, Tc)

    def train_loss(self, params, batch):
        """batch: {'inputs': (B,T)|(B,T,D), 'labels': (B,T)} -> scalar."""
        cfg = self.cfg
        x, aux = self.forward(params, batch["inputs"])
        labels = batch["labels"]
        B, T = labels.shape
        c = min(cfg.loss_chunk, T)
        if T % c:
            c = T
        nchunk = T // c
        if nchunk == 1:
            ce = self._ce_chunk(params, x, labels)
        else:
            xc = jnp.moveaxis(x.reshape(B, nchunk, c, cfg.d_model), 1, 0)
            lc = jnp.moveaxis(labels.reshape(B, nchunk, c), 1, 0)
            ce = jax.lax.map(
                jax.checkpoint(lambda args: self._ce_chunk(params, *args)),
                (xc, lc))
            ce = jnp.moveaxis(ce, 0, 1).reshape(B, T)
        loss = ce.mean()
        if cfg.aux_loss_weight and any(k.endswith("_moe") for k in cfg.pattern):
            loss = loss + cfg.aux_loss_weight * aux / max(len(cfg.pattern), 1)
        return loss

    # ------------------------------------------------------------ serve paths
    def init_caches(self, batch: int, max_len: int, dtype=None):
        """Per-pattern-position stacked decode state (KV caches / SSM states).

        ``dtype`` defaults to the config's compute dtype (``cfg.jdtype``) so
        caches match activations without every call site restating it."""
        if dtype is None:
            dtype = self.cfg.jdtype
        caches = []
        for spec in self.block_specs:
            kind = spec["kind"]
            if kind in ("attn", "attn_moe"):
                one = lambda _=None, s=spec: attn_lib.init_cache(
                    s["mixer"], batch, max_len, dtype)
            elif kind in ("mamba", "mamba_moe"):
                one = lambda _=None, s=spec: s["mixer"].init_state(batch, dtype)
            else:
                one = lambda _=None, s=spec: s["mixer"].init_state(batch, dtype)
            caches.append(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[one() for _ in range(self.n_periods)])
                if self.n_periods > 1 else
                jax.tree.map(lambda x: x[None], one())
            )
        return caches

    def init_paged_caches(self, n_slots: int, n_pages: int, page_size: int,
                          dtype=None):
        """Paged decode caches for the continuous-batching engine: attention
        K/V lives in a global pool of ``(n_pages, page_size, Kh, Dh)`` pages
        per layer (page 0 reserved as the null page), indexed per request by
        a block table the engine owns; ``pos`` stays per-slot. Recurrent
        layers (mamba/rwkv) carry O(1) state, i.e. a single *pinned page*
        per slot — the rows of :meth:`init_caches` — so the engine drives
        all three block families uniformly."""
        if dtype is None:
            dtype = self.cfg.jdtype
        caches = []
        for spec in self.block_specs:
            kind = spec["kind"]
            if kind in ("attn", "attn_moe"):
                one = lambda s=spec: attn_lib.init_paged_cache(
                    s["mixer"], n_slots, n_pages, page_size, dtype)
            else:
                one = lambda s=spec: s["mixer"].init_state(n_slots, dtype)
            caches.append(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[one() for _ in range(self.n_periods)])
                if self.n_periods > 1 else
                jax.tree.map(lambda x: x[None], one())
            )
        return caches

    def paged_cache_axes(self):
        """Logical axes matching :meth:`init_paged_caches`. The page axis is
        unsharded (pages are gathered by id — splitting the pool would turn
        every block-table lookup into a collective); KV heads shard as
        usual, recurrent pinned pages ride the ``batch`` rules."""
        axes = []
        for spec, a in zip(self.block_specs, self.cache_axes()):
            if spec["kind"] in ("attn", "attn_moe"):
                a = {"kp": ("layers", None, None, "kv_heads", None),
                     "vp": ("layers", None, None, "kv_heads", None),
                     "pos": ("layers", "batch")}
            axes.append(a)
        return axes

    def cache_axes(self):
        """Logical axes for the stacked caches (kv_seq shardable)."""
        axes = []
        for spec in self.block_specs:
            kind = spec["kind"]
            if kind in ("attn", "attn_moe"):
                axes.append({"k": ("layers", "batch", "kv_seq", "kv_heads", None),
                             "v": ("layers", "batch", "kv_seq", "kv_heads", None),
                             "pos": ("layers",)})
            elif kind in ("mamba", "mamba_moe"):
                axes.append({"conv": ("layers", "batch", None, "inner"),
                             "h": ("layers", "batch", "inner", None)})
            else:
                axes.append({"S": ("layers", "batch", "kv_heads", None, None),
                             "x_tm": ("layers", "batch", None, None),
                             "x_cm": ("layers", "batch", None, None)})
        return axes

    def _decode_block(self, spec, p, x, cache, layer, block_tables=None,
                      live=None):
        cfg = self.cfg
        kind = spec["kind"]

        def freeze(new_cache):
            # non-live rows (paged engine: mid-chunked-prefill slots) must
            # not advance — the next prefill chunk carries their state
            if live is None:
                return new_cache
            return jax.tree.map(
                lambda new, old: jnp.where(
                    live.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                new_cache, cache)

        h = self._norm(p["norm1"], x)
        if kind in ("attn", "attn_moe"):
            if block_tables is not None:
                y, cache = attn_lib.apply_decode_paged(
                    spec["mixer"], p["mixer"], h, cache, layer, block_tables,
                    live=live)
            else:
                y, cache = attn_lib.apply_decode(spec["mixer"], p["mixer"],
                                                 h, cache)
            x = x + y
        elif kind in ("mamba", "mamba_moe"):
            y, c_new = spec["mixer"].apply(p["mixer"], h, cache)
            cache = freeze(c_new)
            x = x + y
        else:
            mix = spec["mixer"]
            y, s_new, x_tm = mix.time_mix(p["mixer"], h, cache["S"], cache["x_tm"])
            x = x + y
            h2 = self._norm(p["norm2"], x)
            y2, x_cm = mix.channel_mix(p["mixer"], h2, cache["x_cm"])
            x = x + y2
            return x, freeze({"S": s_new, "x_tm": x_tm, "x_cm": x_cm})
        h2 = self._norm(p["norm2"], x)
        if kind.endswith("_moe"):
            y2, _ = spec["ffn"].apply(p["ffn"], h2)
        else:
            y2 = spec["ffn"].apply(p["ffn"], h2)
        return x + y2, cache

    def decode_step(self, params, tokens, caches, block_tables=None,
                    live=None):
        """One token step. tokens: (B,) int32 (or (B,1,D) embeds).

        With ``block_tables`` ((B, P) int32 — the paged engine's per-slot
        page maps, shared by every attention layer), attention layers run
        the paged form against their page pools. ``live`` ((B,) bool) marks
        rows actually decoding: the paged engine MUST pass it, because the
        pool is shared — a non-live row (mid-chunked-prefill) would
        otherwise scatter garbage K/V into real pages and advance the
        recurrent state its next prefill chunk is about to carry. Non-live
        rows compute (fixed batch shape) but write nothing.

        Returns (logits (B, vocab), new caches).
        """
        cfg = self.cfg
        if cfg.frontend == "token":
            x = layers.embed(params["embed"], tokens[:, None]) * float(np.sqrt(cfg.d_model))
        else:
            x = tokens.astype(cfg.jdtype)
        x = self._stream(x)
        new_caches = []
        for spec, pstack, cstack in zip(self.block_specs, params["blocks"], caches):
            def body(x, p, c, layer, spec=spec):
                return self._decode_block(spec, p, x, c, layer,
                                          block_tables=block_tables,
                                          live=live)
            x, c_new = scan_layers(body, x, pstack, cstack)
            new_caches.append(c_new)
        x = self._norm(params["final_norm"], x)
        lg = self.unembed.apply(params["unembed"], x[:, 0])
        return lg, new_caches

    @property
    def spec_decode_supported(self) -> bool:
        """Speculative decoding needs cheap per-position rollback, which the
        paged KV cache gives attention for free (truncate the block table)
        but recurrent state (mamba/rwkv) does not — those archs fall back
        to the one-token decode loop."""
        return all(s["kind"] in ("attn", "attn_moe") for s in self.block_specs)

    def set_paged_pos(self, caches, pos):
        """Overwrite every attention layer's paged-cache ``pos`` leaf with
        the host-authoritative depth ``pos`` (B,). Spec-mode entry point:
        the engine owns the accepted depth, so propose/verify programs set
        it explicitly instead of trusting device-side accumulation — which
        is also what makes rollback free (rejected positions are simply
        re-scattered under the corrected depth next step)."""
        out = []
        for spec, c in zip(self.block_specs, caches):
            if spec["kind"] in ("attn", "attn_moe"):
                c = dict(c)
                c["pos"] = jnp.broadcast_to(
                    pos[None].astype(c["pos"].dtype), c["pos"].shape)
            out.append(c)
        return out

    def verify_step(self, params, tokens, caches, block_tables, live=None):
        """Speculative-verify window: score ``tokens`` (B, Tq) — the pending
        token plus the draft's k proposals — against the paged KV pool in
        ONE dispatch. ``logits[:, i]`` is the target's prediction for the
        token *after* window position ``i``, exactly what
        :meth:`decode_step` would have produced had the window been fed one
        token at a time (same contraction order on the jnp route).

        Attention archs only (see :attr:`spec_decode_supported`); callers
        set the accepted depth first via :meth:`set_paged_pos`. Returns
        ``(logits (B, Tq, vocab), new caches)``; cache ``pos`` leaves are
        left at the entry depth — the host decides how far to advance.
        """
        assert self.spec_decode_supported, \
            "verify_step: recurrent archs cannot roll state back"
        cfg = self.cfg
        x = self._stream(
            layers.embed(params["embed"], tokens) * float(np.sqrt(cfg.d_model)))
        new_caches = []
        for spec, pstack, cstack in zip(self.block_specs, params["blocks"],
                                        caches):
            def body(x, p, c, layer, spec=spec):
                h = self._norm(p["norm1"], x)
                y, c2 = attn_lib.apply_verify_paged(
                    spec["mixer"], p["mixer"], h, c, layer, block_tables,
                    live=live)
                x = x + y
                h2 = self._norm(p["norm2"], x)
                if spec["kind"].endswith("_moe"):
                    y2, _ = spec["ffn"].apply(p["ffn"], h2)
                else:
                    y2 = spec["ffn"].apply(p["ffn"], h2)
                return x + y2, c2
            x, c_new = scan_layers(body, x, pstack, cstack)
            new_caches.append(c_new)
        x = self._norm(params["final_norm"], x)
        lg = self.unembed.apply(params["unembed"], x)
        return lg, new_caches

    def prefill_chunk(self, params, tokens, caches, bt_row, slot, start,
                      chunk_len, final: bool = True):
        """One page-aligned chunk of a single request's prefill (batch 1),
        writing into the paged caches in place of a monolithic
        :meth:`prefill` — the chunked-prefill building block.

        ``tokens: (1, Tc)`` with ``Tc`` a page multiple; ``start`` (scalar,
        page-aligned) is the chunk's global offset — with prefix reuse the
        first chunk starts past the trie-matched pages; ``chunk_len <= Tc``
        is the number of real tokens (final chunk right-padded with zeros).
        ``bt_row: (P,)`` the request's block-table row; ``slot`` the decode
        slot whose recurrent state rows carry across chunks (selected
        branchlessly: at ``start == 0`` the carried state reads as zeros, so
        a slot's previous occupant never leaks in).

        Returns ``(logits (1, vocab) at the chunk's last real token,
        caches)`` — the logits are meaningful on the final chunk, where the
        engine samples the first token. ``final`` is static: non-final
        chunks return ``(None, caches)`` and skip the final norm + unembed
        entirely (the vocab projection dominates a small chunk's FLOPs, and
        only the last chunk's logits are ever read).
        """
        cfg = self.cfg
        slot = jnp.asarray(slot, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        chunk_len = jnp.asarray(chunk_len, jnp.int32)
        x = self._stream(self._embed_inputs(params, tokens))
        Tc = x.shape[1]
        valid = (jnp.arange(Tc)[None, :] < chunk_len)          # (1, Tc)

        def take_row(leaf):
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)

        def put_row(leaf, row):
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, row.astype(leaf.dtype), slot, axis=0)

        def carried(zeros, row):
            # first chunk of a request: ignore the slot's stale state
            return jnp.where(start == 0, zeros, row)

        new_caches = []
        for spec, pstack, cstack in zip(self.block_specs, params["blocks"],
                                        caches):
            kind = spec["kind"]

            def body(x, p, c, layer, spec=spec, kind=kind):
                h = self._norm(p["norm1"], x)
                if kind in ("attn", "attn_moe"):
                    y, c2 = attn_lib.prefill_chunk_paged(
                        spec["mixer"], p["mixer"], h, c, layer, bt_row, slot,
                        start, chunk_len)
                    x = x + y
                elif kind in ("mamba", "mamba_moe"):
                    mix = spec["mixer"]
                    zst = mix.init_state(1, cfg.jdtype)
                    st = jax.tree.map(
                        lambda z, l: carried(z, take_row(l).astype(z.dtype)),
                        zst, {k: c[k] for k in zst})
                    y, s2 = mix.apply(p["mixer"], h, st, valid=valid)
                    x = x + y
                    c2 = {k: put_row(c[k], s2[k]) for k in s2}
                else:  # rwkv
                    mix = spec["mixer"]
                    zst = mix.init_state(1, cfg.jdtype)
                    st = jax.tree.map(
                        lambda z, l: carried(z, take_row(l).astype(z.dtype)),
                        zst, {k: c[k] for k in zst})
                    y, s_new, x_tm = mix.time_mix(p["mixer"], h, st["S"],
                                                  st["x_tm"], valid=valid)
                    x = x + y
                    h2 = self._norm(p["norm2"], x)
                    y2, x_cm = mix.channel_mix(p["mixer"], h2, st["x_cm"],
                                               valid=valid)
                    x = x + y2
                    return x, {"S": put_row(c["S"], s_new),
                               "x_tm": put_row(c["x_tm"], x_tm),
                               "x_cm": put_row(c["x_cm"], x_cm)}
                h2 = self._norm(p["norm2"], x)
                if kind.endswith("_moe"):
                    y2, _ = spec["ffn"].apply(p["ffn"], h2)
                else:
                    y2 = spec["ffn"].apply(p["ffn"], h2)
                return x + y2, c2

            x, c_new = scan_layers(body, x, pstack, cstack)
            new_caches.append(c_new)
        if not final:
            return None, new_caches
        x = self._norm(params["final_norm"], x)
        x_last = jnp.take_along_axis(
            x, jnp.maximum(chunk_len - 1, 0)[None, None, None], axis=1)[:, 0]
        lg = self.unembed.apply(params["unembed"], x_last)
        return lg, new_caches

    def prefill(self, params, inputs, caches):
        """Process a full prompt, filling caches. Returns (last-token logits,
        caches). inputs: (B,T) tokens or (B,T,D) embeds."""
        cfg = self.cfg
        x = self._stream(self._embed_inputs(params, inputs))
        B, T = x.shape[:2]
        new_caches = []
        for spec, pstack, cstack in zip(self.block_specs, params["blocks"], caches):
            kind = spec["kind"]

            def body(x, pc, spec=spec, kind=kind):
                p, c = pc
                h = self._norm(p["norm1"], x)
                if kind in ("attn", "attn_moe"):
                    q, k, v = attn_lib._qkv(
                        spec["mixer"], p["mixer"], h,
                        jnp.broadcast_to(jnp.arange(T)[None], (B, T))
                        if spec["mixer"].rope != "mrope" else
                        jnp.stack([jnp.broadcast_to(jnp.arange(T)[None], (B, T))] * 3))
                    kc = jax.lax.dynamic_update_slice_in_dim(
                        c["k"], k.astype(c["k"].dtype), 0, axis=1)
                    vc = jax.lax.dynamic_update_slice_in_dim(
                        c["v"], v.astype(c["v"].dtype), 0, axis=1)
                    o = attn_lib.attend_full(spec["mixer"], q, k, v)
                    y = spec["mixer"].wo.apply(p["mixer"]["wo"],
                                               o.reshape(B, T, -1))
                    x = x + y
                    c2 = {"k": kc, "v": vc, "pos": jnp.asarray(T, jnp.int32)}
                elif kind in ("mamba", "mamba_moe"):
                    y, c2 = spec["mixer"].apply(p["mixer"], h, None)
                    x = x + y
                else:
                    mix = spec["mixer"]
                    st = mix.init_state(B, cfg.jdtype)
                    y, s_new, x_tm = mix.time_mix(p["mixer"], h, st["S"],
                                                  st["x_tm"])
                    x = x + y
                    h2 = self._norm(p["norm2"], x)
                    y2, x_cm = mix.channel_mix(p["mixer"], h2, st["x_cm"])
                    return x + y2, {"S": s_new, "x_tm": x_tm, "x_cm": x_cm}
                h2 = self._norm(p["norm2"], x)
                if kind.endswith("_moe"):
                    y2, _ = spec["ffn"].apply(p["ffn"], h2)
                else:
                    y2 = spec["ffn"].apply(p["ffn"], h2)
                return x + y2, c2

            x, c_new = jax.lax.scan(body, x, (pstack, cstack))
            new_caches.append(c_new)
        x = self._norm(params["final_norm"], x)
        lg = self.unembed.apply(params["unembed"], x[:, -1])
        return lg, new_caches

    # -------------------------------------------------- mask projection
    def _block_linears(self, spec):
        """(param_key_path, Linear) pairs for one block spec."""
        kind = spec["kind"]
        out = []
        mixer = spec["mixer"]
        if kind in ("attn", "attn_moe"):
            names = ("wq", "wk", "wv", "wo")
        elif kind in ("mamba", "mamba_moe"):
            names = ("w_in", "w_x", "w_dt", "w_out")
        else:
            names = ("wr", "wk", "wv", "wg", "wo", "ck", "cv", "cr")
        out += [(("mixer", n), getattr(mixer, n)) for n in names]
        ffn = spec["ffn"]
        if ffn is not None and hasattr(ffn, "w_up") and not hasattr(ffn, "router"):
            out.append((("ffn", "w_up"), ffn.w_up))
            if ffn.w_gate is not None:
                out.append((("ffn", "w_gate"), ffn.w_gate))
            out.append((("ffn", "w_down"), ffn.w_down))
        return out

    def mask_projection(self, params):
        """Re-apply every binary mask after an optimizer update (paper
        Algorithm 1 line 14). Only affects ``masked_dense`` linears; packed
        and dense params pass through untouched. MoE masked-dense experts are
        projected explicitly."""
        from repro.core import mpd as mpd_lib
        from repro.core.mask import mask_dense as mask_dense_np

        params = dict(params)
        new_blocks = []
        for spec, pstack in zip(self.block_specs, params["blocks"]):
            pstack = jax.tree.map(lambda x: x, pstack)  # shallow copy
            for path, lin in self._block_linears(spec):
                if lin.spec.mode != "masked_dense" or lin.spec.mask is None:
                    continue
                node = pstack
                for k in path[:-1]:
                    node = node[k]
                leaf = node[path[-1]]
                m = jnp.asarray(mask_dense_np(lin.spec.mask), leaf["w"].dtype)
                node[path[-1]] = dict(leaf, w=leaf["w"] * m)
            ffn = spec["ffn"]
            if (ffn is not None and hasattr(ffn, "router")
                    and ffn.mode == "masked_dense"):
                for wk, mask in (("w_up", ffn.mask_up), ("w_gate", ffn.mask_up),
                                 ("w_down", ffn.mask_down)):
                    if mask is None:
                        continue
                    m = jnp.asarray(mask_dense_np(mask),
                                    pstack["ffn"][wk].dtype)
                    pstack["ffn"] = dict(pstack["ffn"],
                                         **{wk: pstack["ffn"][wk] * m})
            new_blocks.append(pstack)
        params["blocks"] = new_blocks
        if (self.unembed.spec.mode == "masked_dense"
                and self.unembed.spec.mask is not None):
            m = jnp.asarray(mask_dense_np(self.unembed.spec.mask),
                            params["unembed"]["w"].dtype)
            params["unembed"] = dict(params["unembed"],
                                     w=params["unembed"]["w"] * m)
        return params

    # ------------------------------------------------------- fold / export
    def to_packed(self, params, *, fuse: bool = False,
                  check_residual: bool = True, atol: float = 1e-6,
                  quantize=None):
        """Fold this trained ``masked_dense`` model into its packed
        inference twin (paper Eq. 2 applied model-wide). Returns
        ``(packed_model, packed_params)``; with ``fuse=True`` the Fig-3
        permutation-cancellation rewrite is applied post hoc, and with
        ``quantize="int8"``/``"int4"`` the packed blocks are additionally
        quantized (scales computed at fold time, round-trip error recorded
        on ``packed_model.quant_report``). See :mod:`repro.core.export`."""
        from repro.core import export as export_lib
        return export_lib.fold_model(self, params, fuse=fuse,
                                     check_residual=check_residual, atol=atol,
                                     quantize=quantize)

    # ------------------------------------------------------------- accounting
    def param_count(self) -> int:
        model = self

        def count(tree):
            return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))

        p = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
        return count(p)

    def active_matmul_params(self) -> int:
        """Matmul parameters touched per token (MODEL_FLOPS = 6·this·tokens).

        Excludes the embedding gather (no FLOPs); MoE counts only top_k routed
        experts plus the shared expert; packed MPD layers count packed size.
        """
        cfg = self.cfg
        total = 0
        for spec in self.block_specs:
            n = 0
            for _, lin in self._block_linears(spec):
                n += lin.param_count()
            ffn = spec["ffn"]
            if ffn is not None and hasattr(ffn, "router"):  # MoE
                n += ffn.router.param_count()
                per_expert = (3 if ffn.gated else 2) * cfg.d_model * cfg.moe_d_ff
                if ffn.mask_up is not None and ffn.mode == "packed":
                    per_expert //= ffn.mask_up.nb
                n += per_expert * ffn.top_k
                if ffn.shared is not None:
                    n += sum(l.param_count() for l in
                             (ffn.shared.w_up, ffn.shared.w_gate,
                              ffn.shared.w_down) if l is not None)
            total += n * self.n_periods
        total += self.unembed.param_count()
        return total


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
