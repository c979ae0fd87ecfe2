#!/usr/bin/env python3
"""Readings that set a cell's comparison limit; not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --first-seed <n> --seeds 12 \
        --control-seeds 3 [--fault page_shift] [--out FILE]

In one process on the cell's chip: the loop is built and warmed once;
then, for each seed, the weights the cell's model code makes from that
seed are served one round of the cell's traffic (its full load, so the
mix's longest requests finish), and the same sample a run checks is
compared with the plain reference.  The program's widest gap over the
seeds is the lower reading.
For the first ``--control-seeds`` seeds the control is read too: the
reference itself computed with float8 e4m3 matmul operands, the precision
below the bfloat16 the configuration serves in, whose first-ranked token
at each served position is held against the float32 reference's best.
The least control gap is the upper reading.  Each reading also carries
the decision a run would make on it (``harness.verdict`` at the cell's
limit).  With ``--fault page_shift`` the program runs with a planted fault
instead: every attention read of the page pool goes one page past the one
its page table names.  One JSON line per seed, then a summary line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def shift_pages() -> None:
    """Plant a pool fault: the physical page of every tile the attention
    kernels read is the next one in the pool, not the page table's."""
    from repro.core import sparsity

    translate = sparsity.translate_tables

    def shifted(kv_index, step_live, page_table, n_pages, **kw):
        phys, virt, live = translate(kv_index, step_live, page_table, n_pages, **kw)
        return (phys + 1) % n_pages, virt, live

    sparsity.translate_tables = shifted


FAULTS = {"page_shift": shift_pages}


def calibrate(root: str, workload: str, seeds: list[int], control_seeds: int) -> dict:
    """The program's and the control's readings over ``seeds``."""
    import harness
    import traffic

    _, cell, config, mix, model = harness.load_cell(root, workload)
    devices = harness.require_chips(cell["chips"])
    limit = float(harness._read_json(os.path.join(
        root, "bench", "limits", f"{workload}.json"))["widest_logit_gap"]["limit"])
    harness.use_checkout_cache(root)
    sess = harness.Session(config, mix, seeds[0], devices, model)
    for specs in sess.warm_specs():
        sess.serve(specs)
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            sess.params = model.make_params(sess.shape, seed)
            sess.loop.params = sess.params
        rd = sess.serve(traffic.make_round(mix, seed, 0, sess.shape.vocab, stream=1))
        sample = harness.check_sample(rd.requests, mix["check_requests"], seed)
        prog = harness.served_gaps(model, sess.params, config, sample)
        failed = sum(len(r.generated) != r.max_new for r in rd.requests)
        row = {"seed": seed, "round_s": rd.end - rd.submit,
               "served": sum(r["tokens"] for r in prog),
               "program": max(r["widest"] for r in prog),
               "program_mismatch": sum(r["mismatch"] for r in prog),
               "program_passes": harness.verdict(prog, failed, limit),
               "finite": all(r["finite"] for r in prog), "failed": failed}
        if i < control_seeds:
            ctl = harness.served_gaps(model, sess.params, config, sample,
                                      control=True)
            row["control"] = max(r["widest"] for r in ctl)
            row["control_mismatch"] = sum(r["mismatch"] for r in ctl)
            row["control_passes"] = harness.verdict(ctl, 0, limit)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    summary = {"workload": workload, "seeds": len(rows), "limit": limit,
               "lower": max(r["program"] for r in rows),
               "upper": min((r["control"] for r in rows if "control" in r),
                            default=None),
               "rows": rows}
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="serve with this fault planted in the program")
    ap.add_argument("--out", default=None, help="also write the summary here")
    args = ap.parse_args()
    # the TPU runtime would otherwise write its logs under /tmp, outside
    # the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if args.fault:
        FAULTS[args.fault]()
    summary = calibrate(ROOT, args.workload, seeds, args.control_seeds)
    summary["fault"] = args.fault
    summary["seconds"] = time.perf_counter() - T_START
    line = json.dumps(summary)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
