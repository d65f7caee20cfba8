"""CLI training driver.

Two paths behind one entry point:

  GP (the paper):  --arch gp-iterative --dataset pol --solver ap --pathwise
                   --warm-start --budget 10
  LM substrate:    --arch llama3-8b --smoke (reduced config on local devices)

The GP path runs real optimisation on this host (CPU-feasible n); the LM
path runs the reduced smoke config — full-scale LM runs are launched on a
TPU fleet with the same train_step after the dry-run proves the sharding.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.runtime import enable_compilation_cache


def run_gp(args):
    from repro.core import OuterConfig, fit, pick_sgd_learning_rate
    from repro.data.synthetic import load_dataset, pad_to_block_multiple
    from repro.gp.hyperparams import HyperParams
    from repro.solvers import SolverConfig
    from repro.train.adam import AdamConfig

    ds = load_dataset(args.dataset, max_n=args.max_n)
    x, y = ds.x_train, ds.y_train
    block = args.block_size if args.solver == "ap" else args.batch_size
    if args.solver in ("ap", "sgd"):
        x, y, _ = pad_to_block_multiple(x, y, block)

    solver = SolverConfig(
        name=args.solver,
        tolerance=args.tolerance,
        max_epochs=args.budget if args.budget > 0 else 1e9,
        precond_rank=args.precond_rank,
        block_size=args.block_size,
        batch_size=args.batch_size,
        learning_rate=args.sgd_lr,
    )
    cfg = OuterConfig(
        estimator="pathwise" if args.pathwise else "standard",
        warm_start=args.warm_start,
        num_probes=args.probes,
        solver=solver,
        adam=AdamConfig(learning_rate=args.lr),
        num_steps=args.steps,
        backend=args.backend,
        bm=args.tile, bn=args.tile,
    )
    key = jax.random.PRNGKey(args.seed)
    if args.solver == "sgd" and args.sgd_lr <= 0:
        lr = pick_sgd_learning_rate(x, y, HyperParams.create(x.shape[1]), cfg,
                                    key)
        print(f"[train] sgd lr grid -> {lr}")
        cfg = OuterConfig(**{**cfg.__dict__, "solver":
                             SolverConfig(**{**solver.__dict__,
                                             "learning_rate": lr})})
    res = fit(
        x, y, cfg, key=key,
        x_test=ds.x_test, y_test=ds.y_test,
        eval_every=args.eval_every,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        verbose=True,
    )
    out = {
        "dataset": ds.name,
        "solver": args.solver,
        "pathwise": args.pathwise,
        "warm_start": args.warm_start,
        "total_time_s": res.wall_time_s,
        "total_epochs": float(res.history["epochs"].sum()),
        "final_res_y": float(res.history["res_y"][-1]),
        "final_res_z": float(res.history["res_z"][-1]),
        "eval_rmse": res.history["eval_rmse"].tolist(),
        "eval_llh": res.history["eval_llh"].tolist(),
    }
    print(json.dumps(out, indent=2))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)


def run_lm(args):
    from repro.configs import SMOKE_SHAPES, get_config
    from repro.data.synthetic import make_lm_batch
    from repro.models import init_params, make_train_step
    from repro.train.adam import adam_init

    cfg = get_config(args.arch, smoke=True)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    opt = adam_init(params)
    step = jax.jit(make_train_step(cfg, num_microbatches=1))
    shape = SMOKE_SHAPES["train_4k"]
    for i in range(args.steps):
        batch = make_lm_batch(jax.random.fold_in(key, i), shape.global_batch,
                              shape.seq_len, cfg.vocab_size)
        if cfg.is_encdec:
            batch = {
                "frames": jax.random.normal(
                    jax.random.fold_in(key, 10_000 + i),
                    (shape.global_batch, shape.seq_len, cfg.d_model)),
                "tokens": batch["tokens"][:, : cfg.decoder_len],
                "labels": batch["labels"][:, : cfg.decoder_len],
                "mask": batch["mask"][:, : cfg.decoder_len],
            }
        elif cfg.frontend.kind == "vision":
            npfx = cfg.frontend.num_prefix
            batch["patch_embeds"] = jax.random.normal(
                jax.random.fold_in(key, 20_000 + i),
                (shape.global_batch, npfx, cfg.frontend.embed_dim))
        params, opt, loss = step(params, opt, batch)
        print(f"[train-lm] {args.arch} step {i}: loss={float(loss):.4f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gp-iterative")
    ap.add_argument("--dataset", default="pol")
    ap.add_argument("--max-n", type=int, default=4000)
    ap.add_argument("--solver", default="cg", choices=["cg", "ap", "sgd"])
    ap.add_argument("--pathwise", action="store_true")
    ap.add_argument("--warm-start", action="store_true")
    ap.add_argument("--probes", type=int, default=64)
    ap.add_argument("--budget", type=float, default=0.0,
                    help="solver epochs per outer step; 0 = to tolerance")
    ap.add_argument("--tolerance", type=float, default=0.01)
    ap.add_argument("--precond-rank", type=int, default=100)
    ap.add_argument("--block-size", type=int, default=1000)
    ap.add_argument("--batch-size", type=int, default=500)
    ap.add_argument("--sgd-lr", type=float, default=0.0)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--backend", default="streamed",
                    choices=["dense", "streamed", "pallas"])
    ap.add_argument("--tile", type=int, default=1024)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    if args.arch == "gp-iterative":
        run_gp(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
