"""What the program itself marks for a traced run: the engine's host spans
(``repro.serve.engine.SPANS``, with their arguments) and the scope path of
each device op (``jax.named_scope`` in the model programs: ``layer_scan``
around the layer scan, ``layer`` around its body).

``trace_reduce`` keeps only the harness's own spans and the ops' short
names. The spans come from the same ``.xplane.pb``. The scopes do not: on a
TPU an op event carries its HLO text and times but not its ``op_name``
metadata, so they are read from the compiled decode and chunk programs,
whose instruction names are the op names in the trace.

A program that writes no spans or scopes (an older tree) gives none, and
the readers built on this return None rather than fail.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import harness

# an HLO instruction and the named-scope path it was traced under
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*\bop_name="([^"]*)"',
                    re.M)


def engine_spans() -> Tuple[str, ...]:
    """The span names the engine writes, or none for a program without
    them."""
    try:
        from repro.serve.engine import SPANS
    except ImportError:
        return ()
    return tuple(SPANS)


def host_spans(path: str, names) -> List[list]:
    """``[name, start_ns, dur_ns, {arg: value}]`` of the host events named
    in ``names`` in the ``.xplane.pb`` at ``path``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                out += [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                         dict(ev.stats)]
                        for ev in ln.events if ev.name in names]
    return sorted(out, key=lambda h: h[1])


def latest_trace() -> Optional[str]:
    """The newest trace the harness wrote under ``<checkout>/.bench_out``."""
    found = glob.glob(os.path.join(harness.ROOT, ".bench_out", "trace-*",
                                   "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def program_scopes(config: Dict, mix: Dict) -> Dict[str, str]:
    """Op short name -> scope path in the cell's decode program and its
    non-final and final chunk programs, from the HLO ``op_name`` metadata
    of each as compiled for the device (a persistent-cache hit after the
    run). They are lowered from shapes, the page pool's included: the
    engine built here holds a pool of two pages, so almost nothing is
    allocated. A name two programs share keeps the decode program's scope;
    every block-table width compiles to the same names."""
    import jax
    import jax.numpy as jnp

    from repro.serve import sampling

    model, layout = harness.build_model(config)
    init = model.init_paged_caches
    eng = harness.build_engine(model, layout,
                               dict(mix, engine=dict(mix["engine"], pages=2)))
    n, cache = eng.n_slots, eng.cache
    pages = mix["engine"].get("pages") or n * cache.max_pages + 1
    pool = jax.eval_shape(lambda: init(n, pages, cache.page_size,
                                       cache.dtype))
    dec, pre = harness.cell_widths(eng, mix)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lowered = [eng._decode_paged.lower(
        layout, pool, eng._dev, i32(n, dec[0]),
        jax.ShapeDtypeStruct((n,), bool), eng._zero_poison)]
    for final in (False, True):
        lowered.append(eng._chunk.lower(
            layout, pool, eng._dev, i32(1, eng.chunk_tokens), i32(pre[0]),
            i32(), i32(), i32(), jax.ShapeDtypeStruct((), jnp.float32),
            i32(), sampling.base_key(0), final=final))
    out: Dict[str, str] = {}
    for low in lowered:
        for name, path in HLO_OP.findall(low.compile().as_text()):
            out.setdefault(name, path)
    return out


def spans(ctx) -> Optional[List[list]]:
    """The engine's spans wholly inside the run's traced slice, read once
    per run (``ctx.engine_spans``); None for a run without a trace."""
    if not hasattr(ctx, "engine_spans"):
        path = latest_trace() if ctx.red is not None else None
        if path is None:
            ctx.engine_spans = None
        else:
            lo, hi = ctx.red["lo"], ctx.red["hi"]
            ctx.engine_spans = [
                h for h in host_spans(path, set(engine_spans()))
                if lo <= h[1] and h[1] + h[2] <= hi]
    return ctx.engine_spans


def scopes(ctx) -> Dict[str, str]:
    """The cell's op scopes, read once per run (``ctx.op_scopes``)."""
    if not hasattr(ctx, "op_scopes"):
        ctx.op_scopes = program_scopes(ctx.config, ctx.mix)
    return ctx.op_scopes


def scan_only(path: str) -> bool:
    """A scope path of an op the layer scan adds, not one of its body."""
    parts = path.split("/")
    return "layer_scan" in parts and "layer" not in parts
