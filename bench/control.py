"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload ms150k.spread --seeds 11 12 13 --control 3

For each seed this runs the cell as a benchmark run does (set-up, a
window of one call, the reference at the stated precision) and prints
the program's readings: the numbers ``correct`` compares.  For the
first ``--control`` seeds it also runs the control -- the reference one
precision step lower (``reference.CONTROL``) in the program's place --
prints its readings against the stated reference, and its ``correct`` as
the driver's own ``judge`` gives it under the cell's limits;
``--all-f32`` adds the reference with every stage in float32, to show
which stage a disagreement comes from.  One JSON line per seed, then a
summary.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import registry  # noqa: E402
import run  # noqa: E402

ALL_F32 = {"rmi": "f32", "proj": "f32", "verify": "f32"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--all-f32", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(registry.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    import reference

    man = registry.manifest()
    device = {"platform": "tpu", "kind": jax.devices()[0].device_kind, "count": 1}
    driver = registry.load_module("drivers", "cluster_loop")
    keys = ("skip_mismatch", "core_mismatch", "noise_mismatch", "member_mismatch",
            "member_left_aside", "label_mismatch", "cluster_mismatch")
    rows = []
    for i, seed in enumerate(args.seeds):
        ctx = run.context(args.workload, seed=seed, seconds=0, trace=0, man=man)
        ctx.t_start = time.perf_counter()

        def extra(outs, ref, corpus, params, z_max, i=i):
            got = {}
            variants = {}
            if i < args.control:
                variants["control"] = reference.CONTROL
            if args.all_f32:
                variants["all_f32"] = ALL_F32
            for name, prec in variants.items():
                t = time.perf_counter()
                alt = reference.cluster(corpus, ctx.cfg, params, z_max, prec)
                got[name] = driver.readings(alt, ref)
                got[name + "_correct"] = driver.judge([got[name]], {}, ctx.limits)[0]
                got[name + "_s"] = time.perf_counter() - t
            return got

        ctx.on_reference = extra
        res = run.run_cell(ctx, man, device)
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: (res["checks"].get(k) or {}).get("value", res["info"].get(k))
                           for k in keys},
               "setup_s": res["metrics"]["setup_s"]["value"],
               "cluster_s": res["metrics"]["cluster_s"]["value"],
               "info": res["info"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {k: max(r["program"][k] for r in rows) for k in keys}
    ctl = [r["info"]["control"] for r in rows if "control" in r["info"]]
    if ctl:
        summary.update({f"control_min_{k}": min(c[k] for c in ctl) for k in keys})
        summary["control_correct"] = [r["info"]["control_correct"] for r in rows
                                      if "control_correct" in r["info"]]
    print("summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
