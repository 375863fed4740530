"""The readings a cell's limits are set from, on the chip at the cell's own
size, in one process:

- the program's sound runs, one per seed (the lower readings);
- the control: the reference put in the program's place and computed in
  bfloat16, the precision below the configuration's float32;
- the faults a training cell can have, planted in the reference put in the
  program's place: half of the batch left out (mean over the rest); the
  exchange between chips left out (each member's own rows, ``1/G`` of the
  batch, on a cell with ``G`` > 1); a step that returns its state unchanged
  (no run needed: its params never move).

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3,... \
        [--planted 3] [--out FILE]

The first ``--planted`` seeds also read the control and the faults.  One
JSON line per seed goes to ``--out`` (and the summary to standard output):
per number, the largest sound reading and the smallest reading of each
planted run.  The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def planted(c: dict, seed: int, pool, ref: dict, groups: int) -> dict:
    """Readings of the control and each fault against the reference."""
    import jax.numpy as jnp

    import compare
    import reference
    fam = harness.family(c["cfg"])
    args = (fam, c["cfg"], c["traffic"]["run"], seed,
            pool[:harness.CHECK_STEPS])
    out = {
        "control_bf16": compare.readings(
            reference.train_steps(*args, dtype=jnp.bfloat16), ref),
        "half_batch": compare.readings(
            reference.train_steps(*args, fraction=0.5), ref),
        "state_unchanged": compare.readings(
            dict(ref, p1=ref["p0"], p_last=ref["p0"]), ref),
    }
    if groups > 1:
        out["exchange_left_out"] = compare.readings(
            reference.train_steps(*args, fraction=1.0 / groups), ref)
    return out


def readings(c: dict, seed: int, with_planted: bool) -> dict:
    import compare
    import reference
    import traffic as traffic_gen
    pool = traffic_gen.make_pool(c["traffic"], c["cfg"], seed)
    run = harness.build(c, seed)
    groups = 1 if run.mesh is None else run.mesh.shape["data"]
    batches = harness.feed(run, pool)
    prog = harness.first_steps(run, batches)
    batches.close()
    run.close()
    del run, batches
    gc.collect()
    ref = reference.train_steps(harness.family(c["cfg"]), c["cfg"],
                                c["traffic"]["run"], seed,
                                pool[:harness.CHECK_STEPS])
    out = {"seed": seed, "program": compare.readings(prog, ref),
           "losses": {"program": prog["losses"], "reference": ref["losses"]}}
    if with_planted:
        out.update(planted(c, seed, pool, ref, groups))
    return out


def summary(rows) -> dict:
    """Per number: the largest sound reading, and per planted run the
    smallest."""
    kinds = sorted({k for r in rows for k in r
                    if k not in ("seed", "program", "losses")})
    numbers = sorted(rows[0]["program"])
    out = {"seeds": [r["seed"] for r in rows]}
    for n in numbers:
        out[n] = {"lower": max(r["program"][n] for r in rows)}
        for k in kinds:
            vals = [r[k][n] for r in rows if k in r]
            out[n][k] = min(vals)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--planted", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        c = harness.cell(json.load(f), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != c["chips"]:
        sys.exit(f"control: {args.workload} needs {c['chips']} TPU chip(s); "
                 f"JAX found {len(devices)} {devices[0].platform}")
    harness.use_compile_cache()
    rows = []
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = readings(c, seed, i < args.planted)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        if sink:
            sink.write(json.dumps(row) + "\n")
            sink.flush()
    print(json.dumps({"workload": args.workload, **summary(rows)}))


if __name__ == "__main__":
    main()
