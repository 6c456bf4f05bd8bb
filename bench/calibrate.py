"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--fault half|token|token_rare|exchange] \
        [--out readings.json]

For each seed: the program's readings from its first three steps, through
the same session, feed and compiled programs a benchmark run uses, and the
float32 reference's; the three compared numbers between them.  For each
control seed also the control's numbers: the reference computed with
float8 (e4m3) matmuls put in the program's place.  With ``--fault`` the
timed path is broken underneath first:

* ``half``: the loss leaves out the second half of each sequence's tokens
  and takes its mean over the rest;
* ``token``: the feed alters one token of each sequence where it produces
  it, to the neighbouring id; ``token_rare``: to the last id of the
  vocabulary, which the Zipfian draws all but never give;
* ``exchange``: the CP islands leave out the KV exchange between chips.

(A step that returns its state unchanged reads 1 on ``delta_leaf`` by the
comparison's own measure and needs no run.)  Needs the cell's chips; no
measured window is run.  The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def plant(fault: str, vocab: int):
    """Break the program's timed path; returns the feed transform, if
    any."""
    if fault == "half":
        import repro.launch.steps as steps
        real = steps.loss_fn

        def half(params, cfg, ctx, batch, **kw):
            lab = batch["labels"]
            lab = lab.at[..., lab.shape[-1] // 2:].set(-1)
            return real(params, cfg, ctx, {**batch, "labels": lab}, **kw)

        steps.loss_fn = half
        return None
    if fault in ("token", "token_rare"):
        def alter(hb):
            toks = hb.arrays["tokens"].copy()
            first = (toks >= 0).argmax(axis=1)
            rows = range(toks.shape[0])
            t = toks[rows, first]
            if fault == "token":
                toks[rows, first] = np.where(t > 0, t - 1, t + 1)
            else:
                toks[rows, first] = vocab - 1 - (t == vocab - 1)
            hb.arrays["tokens"] = toks
            return hb
        return alter
    if fault == "exchange":
        import repro.core.cp_attention as cpa
        cpa._run_hops = lambda init_part, *a, **k: init_part
        return None
    raise ValueError(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctl_seeds = [int(x) for x in args.control_seeds.split(",") if x]

    import run as bench_run
    bench_run.setup_compile_cache()
    import jax.numpy as jnp

    from harness import check
    from harness.catalog import load_cell, load_module
    from harness.device import load_peaks, pick_devices
    from harness.spans import Spans

    cell = load_cell(args.workload, ROOT)
    devices = pick_devices(cell.chips, load_peaks(cell.bench_dir))
    runner = load_module(cell.bench_dir / "runners" / "train.py")
    reference = load_module(cell.bench_dir / "reference"
                            / f"{cell.config['family']}.py")
    transform = (plant(args.fault, cell.config["vocab_size"])
                 if args.fault else None)
    log = bench_run.log
    session = runner.Session(cell, devices, log)
    memo: dict = {}
    rows = []
    from harness.weights import leaf_names
    leaves = leaf_names(session.params_abs)
    for seed in sorted(set(seeds) | set(ctl_seeds)):
        t0 = time.perf_counter()
        session.prepare(seed, runner.CHECK_STEPS)
        trainer = session.start(seed, Spans(), transform)
        try:
            # the program's starting weights against the seeded values the
            # comparison measures changes from: 0 when they are the same
            init_gap = float(np.max(np.asarray(session.change_norms(
                trainer.state["params"], trainer.words))))
            prog = trainer.first_steps()
        finally:
            trainer.close()
        ref = runner.reference_readings(cell, seed, session.params_abs,
                                        reference, devices, log=log,
                                        memo=memo)
        row = {"seed": seed, "fault": args.fault or None,
               "program": check.compare(prog, ref), "init_gap": init_gap,
               "prog_losses": prog.losses, "ref_losses": ref.losses}
        if seed in ctl_seeds:
            ctl = runner.reference_readings(
                cell, seed, session.params_abs, reference, devices,
                mm_dtype=jnp.float8_e4m3fn, log=log, memo=memo)
            row["control"] = check.compare(ctl, ref)
            row["ctl_losses"] = ctl.losses
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        row["detail"] = {"leaves": leaves, "program": prog.as_dict(),
                         "reference": ref.as_dict()}
        rows.append(row)
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
