"""Production training launcher: --arch <id> on the current device topology.

On a real TPU slice this runs under `python -m repro.launch.train --arch
granite-8b`; on this CPU container use the smoke configs (--smoke) — the
code path (mesh, sharding rules, fault-tolerant loop) is identical.
"""

import argparse

import jax

from repro.configs.common import ARCHS, get_config
from repro.data import SyntheticLM
from repro.dist import sharding as sh
from repro.dist.mesh import make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.optim import OptConfig
from repro.train import TrainConfig, run


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCHS, required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--mpd-c", type=int, default=0, help="0 = config default")
    p.add_argument("--mpd-fuse", action="store_true")
    p.add_argument("--mpd-mode", choices=("", "packed", "masked_dense"),
                   default="", help="override the config's training "
                   "parameterization (masked_dense = paper-faithful)")
    p.add_argument("--fold-to-packed", action="store_true",
                   help="after training, fold the masked_dense weights into "
                   "a packed deployment checkpoint (<ckpt-dir>/packed); "
                   "--mpd-fuse additionally applies the Fig-3 perm-fusion "
                   "rewrite so FFNs hit the one-dispatch fused kernel")
    p.add_argument("--quantize", choices=("", "int8", "int4"), default="",
                   help="with --fold-to-packed: quantize the packed export "
                   "(int8 execution; int4 = nibble-packed storage)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--data-axis", type=int, default=0,
                   help="mesh data-axis size (0 = all devices)")
    args = p.parse_args(argv)
    enable_compile_cache()

    over = {}
    if args.mpd_c:
        over["mpd_c"] = args.mpd_c
    if args.mpd_fuse:
        over["mpd_fuse"] = True
    if args.mpd_mode:
        over["mpd_mode"] = args.mpd_mode
    if args.quantize and not args.fold_to_packed:
        raise SystemExit("--quantize quantizes the packed export; add "
                         "--fold-to-packed")
    if args.fold_to_packed:
        if not args.ckpt_dir:
            raise SystemExit("--fold-to-packed needs --ckpt-dir for the "
                             "packed export")
        if over.setdefault("mpd_mode", "masked_dense") != "masked_dense":
            raise SystemExit("--fold-to-packed folds a masked_dense run; "
                             "drop --mpd-mode packed")
    cfg = get_config(args.arch, smoke=args.smoke, **over)
    if cfg.frontend != "token":
        raise SystemExit(f"{args.arch} uses an embedding frontend; "
                         "use examples/ or the dry-run for this arch")
    model = build(cfg)
    print(f"{cfg.name}: {model.param_count():,} params")

    n_dev = jax.device_count()
    n_data = args.data_axis or n_dev
    mesh = rules = None
    if n_dev > 1:
        mesh = make_mesh((n_data, n_dev // n_data), ("data", "model"))
        rules = sh.tp_rules()
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq_len,
                       global_batch=args.global_batch, seed=0)
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, clip_norm=1.0, schedule="cosine",
                      warmup_steps=min(20, args.steps // 5),
                      total_steps=args.steps),
        grad_compress_bits=8 if args.compress_grads else 0,
        ckpt_dir=args.ckpt_dir, ckpt_every=50 if args.ckpt_dir else 0)
    out = run(model, tcfg, data, num_steps=args.steps, mesh=mesh, rules=rules)
    print(f"final loss {out['history'][-1]:.4f}")

    if args.fold_to_packed:
        import dataclasses

        from repro.checkpoint import checkpoint as ckpt_lib
        d = ckpt_lib.export_packed(args.ckpt_dir, args.steps, model,
                                   out["params"], fuse=args.mpd_fuse,
                                   quantize=args.quantize or None)
        n_pk = build(dataclasses.replace(cfg, mpd_mode="packed")).param_count()
        print(f"packed export: {d} "
              f"({n_pk:,} params, was {model.param_count():,}"
              + (f", {args.quantize}-quantized" if args.quantize else "")
              + ")")


if __name__ == "__main__":
    main()
