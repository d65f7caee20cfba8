"""One-program multi-scenario sweeps: kernel x seed x solver-config grids
as vmap lanes.

Partitions a ``configs.gp_iterative.KERNEL_SWEEP`` x seed x numerics grid
by STATIC signature — kernel kind, solver name, estimator, shapes — and
runs each group as ONE process and ONE compiled executable: seeds become
vmap lanes inside a single scan-of-steps program (``core.driver.fit_batch``)
instead of the one-subprocess-per-cell pattern of ``launch.sweep``, and
numeric solver settings (tolerance / epoch budget / SGD lr — a sweep over
the paper's early-stopping and compute-budget knobs) ride as a lane-stacked
traced ``SolverNumerics`` pytree, so a tolerance x lr grid does NOT retrace.
A ``--precond-ranks`` grid is the static counterexample: rank changes the
preconditioner's shapes, so each rank is its own group (and executable) and
its cells carry an ``__rk<r>`` artifact tag.
Per-cell JSON artifacts and the ``_sweep_status.json`` summary keep the
sweep-output conventions (done cells are skipped on re-run, so the sweep is
resumable).

    PYTHONPATH=src python -m repro.launch.batch --out artifacts/batch \
        --dataset pol --max-n 512 --kernels matern12,matern32 --seeds 2 \
        --steps 5 --smoke --tolerances 0.01,0.05 --sgd-lrs 0.5,1.0

``--shard-lanes`` additionally shards the lane axis of every group across
the local devices (1-D lane mesh, ``launch.mesh.make_lane_mesh``): the same
one-executable program runs data-parallel over lanes, which is how a TPU
slice runs the whole grid at full occupancy. Groups whose lane count does
not divide the device count fall back to the unsharded path with a note.

``--isolate`` falls back to one subprocess per cell (jax memory hygiene /
fault isolation, as in ``launch.sweep``); the artifacts are identical, so
the two modes are interchangeable and A/B-able (benchmarks/batched_sweep,
benchmarks/sharded_sweep). ``--expect-one-compile-per-group`` asserts the
one-executable contract via jit-cache retrace counting and fails the run
when it is violated.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import NamedTuple, Optional

from repro.configs.gp_iterative import KERNEL_SWEEP, SMOKE, GPArchConfig
from repro.runtime import enable_compilation_cache


class Cell(NamedTuple):
    """One sweep cell: an arch at one seed and one solver setting.

    ``rank`` (preconditioner rank) is the one STATIC solver axis a sweep
    may grid over: unlike the traced tolerance/lr/budget axes it changes
    array shapes, so cells differing in rank land in different static
    groups (one executable per rank — the minimal form of the ROADMAP
    per-lane-preconditioner follow-up, which needs shape bucketing to go
    further).
    """

    arch: GPArchConfig
    seed: int
    tolerance: float
    lr: float
    epochs: float
    rank: int  # preconditioner rank (static: partitions groups)
    tag: str  # filename suffix for the numeric axes ("" for 1-point grids)


def cell_filename(arch_name: str, seed: int, tag: str = "") -> str:
    return f"{arch_name}__s{seed}{tag}.json"


def cell_done(out_dir: str, arch_name: str, seed: int, tag: str = "") -> bool:
    return os.path.exists(
        os.path.join(out_dir, cell_filename(arch_name, seed, tag))
    )


def sweep_archs(kernels: list[str] | None, smoke: bool) -> list[GPArchConfig]:
    """KERNEL_SWEEP entries (optionally filtered), at SMOKE sizes if asked."""
    archs = list(KERNEL_SWEEP)
    if kernels:
        archs = [a for a in archs if a.kind in kernels]
        missing = set(kernels) - {a.kind for a in archs}
        if missing:
            raise KeyError(f"kernels not in KERNEL_SWEEP: {sorted(missing)}")
    if smoke:
        archs = [
            dataclasses.replace(
                a, num_probes=SMOKE.num_probes,
                num_rff_pairs=SMOKE.num_rff_pairs,
                solver_epochs=SMOKE.solver_epochs,
            )
            for a in archs
        ]
    return archs


def _parse_grid(text: Optional[str], default: float) -> list[float]:
    if not text:
        return [default]
    return [float(v) for v in text.split(",")]


def make_cells(archs: list[GPArchConfig], seeds: list[int], args) -> list[Cell]:
    """arch x seed x tolerance x lr x epoch-budget x precond-rank grid, with
    filename tags only for the solver axes that actually have more than one
    point (so plain kernel x seed sweeps keep their legacy artifact names)."""
    tols = _parse_grid(args.tolerances, args.tolerance)
    lrs = _parse_grid(args.sgd_lrs, args.sgd_lr)
    budgets = _parse_grid(getattr(args, "epoch_budgets", None), 0.0)
    # Preconditioner ranks are ints and STATIC (see Cell); None defers to
    # each arch's own precond_rank.
    ranks_text = getattr(args, "precond_ranks", None)
    ranks = ([int(v) for v in ranks_text.split(",")] if ranks_text
             else [None])
    cells = []
    seen: set = set()  # colliding grid points (e.g. "0.01,0.01", or an
    # explicit budget equal to the arch default with 0 also given) would
    # otherwise run redundant lanes AND write the same artifact path twice.
    for arch in archs:
        for seed in seeds:
            for tol in tols:
                for lr in lrs:
                    for ep in budgets:
                        for rk in ranks:
                            epochs = ep or float(arch.solver_epochs)
                            rank = rk if rk is not None else arch.precond_rank
                            parts = []
                            if len(tols) > 1:
                                parts.append(f"tol{tol:g}")
                            if len(lrs) > 1:
                                parts.append(f"lr{lr:g}")
                            if len(budgets) > 1:
                                parts.append(f"ep{epochs:g}")
                            if len(ranks) > 1:
                                parts.append(f"rk{rank:g}")
                            tag = "".join("__" + p for p in parts)
                            cell = Cell(arch, seed, tol, lr, epochs, rank,
                                        tag)
                            if cell not in seen:
                                seen.add(cell)
                                cells.append(cell)
    # Distinct cells must not share an artifact path (the %g tags keep 6
    # significant digits): a silent collision would overwrite one cell's
    # JSON with another's and make the loser unrecoverable on resume.
    by_path: dict = {}
    for c in cells:
        path = cell_filename(c.arch.name, c.seed, c.tag)
        if path in by_path:
            raise ValueError(
                f"grid cells {by_path[path][2:-1]} and {c[2:-1]} collide on "
                f"artifact name {path!r}; choose grid values that differ "
                f"within 6 significant digits"
            )
        by_path[path] = c
    return cells


def solver_config_for(arch: GPArchConfig, args, cell: Optional[Cell] = None):
    """The FULL per-cell SolverConfig (numeric values included)."""
    from repro.solvers import SolverConfig

    solver = args.solver or arch.solver
    return SolverConfig(
        name=solver,
        tolerance=cell.tolerance if cell else args.tolerance,
        kind=arch.kind,
        max_epochs=float(cell.epochs if cell else arch.solver_epochs),
        precond_rank=cell.rank if cell else arch.precond_rank,
        block_size=args.block_size,
        batch_size=args.batch_size,
        learning_rate=cell.lr if cell else args.sgd_lr,
    )


def outer_config_for(arch: GPArchConfig, args, cell: Optional[Cell] = None,
                     static: bool = False):
    """The OuterConfig of one sweep cell.

    ``static=True`` strips the solver's numeric fields to their canonical
    defaults (``solvers.strip_numerics``): the result is the hashable GROUP
    KEY — and the jit static argument — under which every numeric cell of
    the grid shares one executable, with the actual numbers delivered as a
    lane-stacked traced ``SolverNumerics``.
    """
    from repro.core import OuterConfig
    from repro.solvers import strip_numerics

    scfg = solver_config_for(arch, args, cell)
    if static:
        scfg = strip_numerics(scfg)
    return OuterConfig(
        estimator=arch.estimator,
        warm_start=arch.warm_start,
        num_probes=arch.num_probes,
        num_rff_pairs=arch.num_rff_pairs,
        kind=arch.kind,
        solver=scfg,
        num_steps=args.steps,
        bm=args.bm,
        bn=args.bn,
    )


def cell_numerics(cell: Cell, args):
    """The cell's traced numeric settings (scalar-leaf SolverNumerics)."""
    from repro.solvers import numerics_of

    return numerics_of(solver_config_for(cell.arch, args, cell))


def group_cells(cells: list[Cell], args):
    """Static signature -> member cells.

    The signature is the jit static argument itself (the hashable
    numerics-stripped OuterConfig); cells that share it share one
    executable. With a shared dataset that means one group per kernel kind
    x preconditioner rank — the tolerance/lr/budget grid rides as traced
    lane data, while a ``--precond-ranks`` grid partitions (rank changes
    the preconditioner's shapes, so mixing ranks in one lane group is
    impossible without shape bucketing) — and the partition stays correct
    for any future per-cell static divergence.
    """
    groups: dict = {}
    for cell in cells:
        key = outer_config_for(cell.arch, args, cell, static=True)
        groups.setdefault(key, []).append(cell)
    return groups


def _load_data(archs: list[GPArchConfig], args):
    """Shared (x, y), padded for every block solver any cell will run."""
    import math

    from repro.data.synthetic import load_dataset, pad_to_block_multiple

    ds = load_dataset(args.dataset, max_n=args.max_n, split=args.split)
    x, y = ds.x_train, ds.y_train
    solvers = {args.solver or a.solver for a in archs}
    blocks = [args.block_size if s == "ap" else args.batch_size
              for s in solvers if s in ("ap", "sgd")]
    if blocks:
        x, y, _ = pad_to_block_multiple(x, y, math.lcm(*blocks))
    return x, y


def _cell_record(cell: Cell, res, mode: str, group_size: int) -> dict:
    hist = res.history
    return {
        "arch": cell.arch.name,
        "kernel": cell.arch.kind,
        "seed": cell.seed,
        "tolerance": cell.tolerance,
        "learning_rate": cell.lr,
        "max_epochs": cell.epochs,
        "precond_rank": cell.rank,
        "mode": mode,
        "lanes": group_size,
        "wall_time_s": res.wall_time_s,
        "final_hypers": [float(v) for v in hist["hypers"][-1]],
        "history": {
            "res_y": [float(v) for v in hist["res_y"]],
            "res_z": [float(v) for v in hist["res_z"]],
            "iters": [int(v) for v in hist["iters"]],
            "epochs": [float(v) for v in hist["epochs"]],
        },
    }


def _write_cell(out_dir: str, cell: Cell, record: dict):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, cell_filename(cell.arch.name, cell.seed, cell.tag)
    )
    with open(path, "w") as f:
        json.dump(record, f, indent=2)


def _scan_cache_size():
    """jit-cache size of ``core.outer.outer_scan`` — the retrace counter.

    Returns None (never 0) when the private jit introspection API is
    unavailable, so one-compile-per-group checks cannot pass vacuously
    (same contract as ``serve.engine.num_compiles``)."""
    from repro.core.outer import outer_scan

    try:
        return int(outer_scan._cache_size())
    except Exception:  # noqa: BLE001 - private API; absence is not an error
        return None


def run_batched(cells, x, y, args) -> dict:
    """All groups in-process: one fit_batch (= one executable) per group.

    Every cell of a group — across member archs AND across the numeric
    tolerance/lr/budget grid, not just across seeds — joins the same
    fit_batch call, so a group really is one program. ``--shard-lanes``
    additionally places the lane axis on a 1-D device mesh."""
    import jax

    from repro.core import fit_batch
    from repro.solvers import stack_numerics

    mesh = None
    if args.shard_lanes:
        from repro.launch.mesh import make_lane_mesh

        mesh = make_lane_mesh()
        print(f"[batch] lane mesh: {mesh.devices.size} device(s)")

    compiles0 = _scan_cache_size()
    failures, num_groups, num_cells = [], 0, 0
    sharded_groups = 0
    groups = group_cells(cells, args)
    for cfg, members in groups.items():
        todo = [c for c in members
                if not cell_done(args.out, c.arch.name, c.seed, c.tag)]
        for c in members:
            if c not in todo:
                print(f"[batch] skip (done): {c.arch.name} s{c.seed}{c.tag}")
        if not todo:
            continue
        num_groups += 1
        label = ",".join(sorted({c.arch.name for c in todo}))
        t0 = time.time()
        keys = jax.numpy.stack([jax.random.PRNGKey(c.seed) for c in todo])
        nums = stack_numerics([cell_numerics(c, args) for c in todo])
        group_mesh = mesh
        if mesh is not None and len(todo) % mesh.devices.size != 0:
            print(f"[batch] note: group {label} has {len(todo)} lanes, not "
                  f"a multiple of {mesh.devices.size} devices; running "
                  f"unsharded")
            group_mesh = None
        try:
            results = fit_batch(x, y, cfg, keys, numerics=nums,
                                mesh=group_mesh)
        except Exception as e:  # noqa: BLE001 - sweep must keep going
            print(f"[batch] FAIL group {label}: {e}", file=sys.stderr)
            failures.extend(
                [(c.arch.name, c.seed, c.tag) for c in todo])
            continue
        dt = time.time() - t0
        if group_mesh is not None:
            sharded_groups += 1
        shard_note = (f", sharded x{mesh.devices.size}"
                      if group_mesh is not None else "")
        print(f"[batch] OK {label} x {len(todo)} lanes ({dt:.1f}s"
              f"{shard_note})", flush=True)
        for c, res in zip(todo, results):
            _write_cell(args.out, c, _cell_record(c, res, "batched",
                                                  len(todo)))
            num_cells += 1
    compiles1 = _scan_cache_size()
    num_compiles = (None if compiles0 is None or compiles1 is None
                    else compiles1 - compiles0)
    return {
        "failures": failures,
        "groups": num_groups,
        "num_compiles": num_compiles,
        "cells": num_cells,
        "mode": "batched",
        # Only claim sharding that actually happened: a mesh was built AND
        # at least one executed group used it (groups whose lane count does
        # not divide the device count fall back to unsharded).
        "shard_devices": (mesh.devices.size
                          if mesh is not None and sharded_groups else 0),
        "sharded_groups": sharded_groups,
    }


def run_isolated(cells, args, argv_passthrough: list[str]) -> dict:
    """Subprocess-per-cell fallback (the legacy ``launch.sweep`` pattern).

    Each cell's numeric settings travel as plain worker flags — one process
    AND one executable per numeric cell, which is exactly the compile cost
    the traced-numerics batched path amortises away
    (benchmarks/sharded_sweep A/Bs the two)."""
    failures, num_cells = [], 0
    for c in cells:
        if cell_done(args.out, c.arch.name, c.seed, c.tag):
            print(f"[batch] skip (done): {c.arch.name} s{c.seed}{c.tag}")
            continue
        cmd = [
            sys.executable, "-m", "repro.launch.batch",
            "--only-cell", f"{c.arch.kind}:{c.seed}",
            "--tolerance", str(c.tolerance),
            "--sgd-lr", str(c.lr),
            "--solver-epochs", str(c.epochs),
            "--precond-rank", str(c.rank),
        ] + (["--cell-tag", c.tag] if c.tag else []) + argv_passthrough
        # Workers must import repro regardless of cwd / install mode:
        # prepend this package's src dir, keep the inherited PYTHONPATH.
        src = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        inherited = os.environ.get("PYTHONPATH")
        pypath = src + (os.pathsep + inherited if inherited else "")
        t0 = time.time()
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=args.timeout,
            env={**os.environ, "PYTHONPATH": pypath},
        )
        dt = time.time() - t0
        if r.returncode == 0:
            num_cells += 1
            print(f"[batch] OK {c.arch.name} s{c.seed}{c.tag} ({dt:.1f}s)",
                  flush=True)
        else:
            failures.append((c.arch.name, c.seed, c.tag))
            print(f"[batch] FAIL {c.arch.name} s{c.seed}{c.tag} ({dt:.1f}s)\n"
                  f"{(r.stderr or r.stdout)[-2000:]}", flush=True)
    return {
        "failures": failures,
        "groups": num_cells,  # one executable (and process) per cell
        "num_compiles": None,  # spread over subprocesses; unknowable here
        "cells": num_cells,
        "mode": "isolated",
        "shard_devices": 0,
        "sharded_groups": 0,
    }


def run_single_cell(archs, args) -> int:
    """--only-cell kernel:seed — one cell in this process (isolate worker).

    The cell's numeric settings arrive as the worker's --tolerance /
    --sgd-lr / --solver-epochs scalars and are baked into the static config
    (a single cell has nothing to group with)."""
    import jax

    from repro.core import fit
    from repro.runtime import require_no_cpu_fallback

    require_no_cpu_fallback()
    kind, seed = args.only_cell.rsplit(":", 1)
    seed = int(seed)
    matches = [a for a in archs if a.kind == kind]
    if not matches:
        print(f"[batch] unknown cell kernel {kind!r}", file=sys.stderr)
        return 1
    arch = matches[0]
    epochs = float(args.solver_epochs) if args.solver_epochs else float(
        arch.solver_epochs)
    rank = (args.precond_rank if args.precond_rank is not None
            else arch.precond_rank)
    cell = Cell(arch, seed, args.tolerance, args.sgd_lr, epochs, rank,
                args.cell_tag)
    cfg = outer_config_for(arch, args, cell)
    x, y = _load_data([arch], args)
    res = fit(x, y, cfg, key=jax.random.PRNGKey(seed), steps_per_round=0)
    _write_cell(args.out, cell, _cell_record(cell, res, "isolated", 1))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="artifacts/batch")
    ap.add_argument("--dataset", default="pol")
    ap.add_argument("--max-n", type=int, default=512)
    ap.add_argument("--split", type=int, default=0)
    ap.add_argument("--kernels", default=None,
                    help="comma list (default: every KERNEL_SWEEP kernel)")
    ap.add_argument("--seeds", type=int, default=2,
                    help="seed grid 0..seeds-1 per kernel")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="SMOKE probe/RFF/budget sizes")
    ap.add_argument("--solver", default=None, choices=[None, "cg", "ap", "sgd"],
                    help="override the sweep's solver")
    ap.add_argument("--tolerance", type=float, default=0.01)
    ap.add_argument("--tolerances", default=None,
                    help="comma floats: solver-tolerance grid (traced — "
                         "every point shares the group's one executable)")
    ap.add_argument("--block-size", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--sgd-lr", type=float, default=2.0)
    ap.add_argument("--sgd-lrs", default=None,
                    help="comma floats: SGD learning-rate grid (traced)")
    ap.add_argument("--epoch-budgets", default=None,
                    help="comma floats: solver epoch-budget grid (traced); "
                         "0 means the arch's default budget")
    ap.add_argument("--precond-ranks", default=None,
                    help="comma ints: preconditioner-rank grid (STATIC — "
                         "rank changes shapes, so each rank is its own "
                         "group/executable; cells gain an __rk<r> tag)")
    ap.add_argument("--shard-lanes", action="store_true",
                    help="shard each group's lane axis across local devices "
                         "(1-D lane mesh)")
    ap.add_argument("--bm", type=int, default=256)
    ap.add_argument("--bn", type=int, default=256)
    ap.add_argument("--isolate", action="store_true",
                    help="legacy one-subprocess-per-cell sweep")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--only-cell", default=None,
                    help="internal: run one kernel:seed cell in-process")
    ap.add_argument("--solver-epochs", type=float, default=0.0,
                    help="internal (isolate worker): the cell's epoch budget")
    ap.add_argument("--precond-rank", type=int, default=None,
                    help="internal (isolate worker): the cell's "
                         "preconditioner rank")
    ap.add_argument("--cell-tag", default="",
                    help="internal (isolate worker): artifact filename tag")
    ap.add_argument("--expect-one-compile-per-group", action="store_true",
                    help="fail unless retraces == executed groups")
    args = ap.parse_args(argv)
    # Sets a config value only: the --isolate parent stays off the chip.
    enable_compilation_cache()

    kernels = args.kernels.split(",") if args.kernels else None
    archs = sweep_archs(kernels, args.smoke)
    seeds = list(range(args.seeds))

    if args.only_cell:
        return run_single_cell(archs, args)

    cells = make_cells(archs, seeds, args)
    t0 = time.time()
    if args.isolate:
        # Reconstruct the cell-relevant flags for the worker subprocesses
        # (numeric settings are appended per cell by run_isolated).
        passthrough = [
            "--out", args.out, "--dataset", args.dataset,
            "--max-n", str(args.max_n), "--split", str(args.split),
            "--steps", str(args.steps),
            "--block-size", str(args.block_size),
            "--batch-size", str(args.batch_size),
            "--bm", str(args.bm), "--bn", str(args.bn),
        ]
        if args.smoke:
            passthrough.append("--smoke")
        if args.solver:
            passthrough += ["--solver", args.solver]
        status = run_isolated(cells, args, passthrough)
    else:
        x, y = _load_data(archs, args)
        status = run_batched(cells, x, y, args)

    status["wall_time_s"] = time.time() - t0
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "_sweep_status.json"), "w") as f:
        json.dump(status, f, indent=2)
    print(f"[batch] {status['cells']} cells in {status['wall_time_s']:.1f}s "
          f"({status['groups']} groups, compiles={status['num_compiles']}, "
          f"{len(status['failures'])} failures)")

    ok = not status["failures"]
    if args.expect_one_compile_per_group and not args.isolate:
        if status["num_compiles"] is None:
            # Introspection unavailable must FAIL the check, not pass it
            # vacuously (cf. serve.engine.num_compiles contract).
            print("[batch] RETRACE CHECK UNAVAILABLE: jit cache "
                  "introspection missing", file=sys.stderr)
            ok = False
        elif status["num_compiles"] != status["groups"]:
            print(f"[batch] RETRACE VIOLATION: {status['num_compiles']} "
                  f"compiles for {status['groups']} groups", file=sys.stderr)
            ok = False
        else:
            print(f"[batch] one executable per group verified "
                  f"({status['groups']} groups == {status['num_compiles']} "
                  f"compiles)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
